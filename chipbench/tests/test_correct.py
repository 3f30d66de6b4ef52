"""``correct`` has to come out false under the control and under each fault
a training cell can have, and the harness must refuse a machine without an
accelerator.  Tiny sizes on the CPU, with the limits the tiny
configurations state; on the chip the same comparisons run at the cells'
sizes (``calibrate.py``)."""
import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

import repro.state_store.train_wal as train_wal
from chipbench import run as R


def _run(bench, cpu, workload, seed=2**35 + 1):
    return R.run_cell(bench, workload, seed, 0.0, False, chips=cpu)


def _patch_step(monkeypatch, make):
    """Replace the compiled step the cell drives with ``make(trainer)``."""
    build = R.build_trainer

    def patched(cfg, seed):
        tr = build(cfg, seed)
        tr.step = make(tr)
        return tr
    monkeypatch.setattr(R, "build_trainer", patched)


@pytest.mark.parametrize("workload", ["tiny_whisper.save10",
                                      "tiny_qwen2.save10"])
def test_control_is_not_correct(bench, cpu, monkeypatch, workload):
    """The reference in float8 put in the program's place."""
    first = R.first_steps
    seen = {}

    def control(tr, mix, wal):
        state, _ = first(tr, mix, wal)
        return state, R.reference_readings(tr, seen["seed"], mm="fp8")

    build = R.build_trainer

    def remember(cfg, seed):
        seen["seed"] = seed
        return build(cfg, seed)
    monkeypatch.setattr(R, "build_trainer", remember)
    monkeypatch.setattr(R, "first_steps", control)
    out = _run(bench, cpu, workload)
    assert not out["correct"], out["checks"]


def test_state_left_unchanged_is_not_correct(bench, cpu, monkeypatch):
    def make(tr):
        step = tr.step
        return lambda state, batch: (state, step(state, batch)[1])
    _patch_step(monkeypatch, make)
    out = _run(bench, cpu, "tiny_whisper.save10")
    assert not out["correct"]
    assert out["checks"]["update_gap"]["value"] == pytest.approx(1.0)


@pytest.mark.parametrize("workload", ["tiny_whisper.save10",
                                      "tiny_qwen2.save10"])
def test_half_the_batch_left_out_is_not_correct(bench, cpu, monkeypatch,
                                                workload):
    from repro.launch.train import make_train_step
    from repro.models import build_model
    from repro.optim import AdamWConfig

    def make(tr):
        hp = dict(tr.cfg["optimizer"], betas=tuple(tr.cfg["optimizer"]["betas"]))
        step = make_train_step(build_model(tr.program_cfg), AdamWConfig(**hp))
        half = tr.cfg["job"]["batch"] // 2
        return lambda state, batch: step(
            state, {k: v[:half] for k, v in batch.items()})
    _patch_step(monkeypatch, make)
    out = _run(bench, cpu, workload)
    assert not out["correct"], out["checks"]


def test_saved_chunk_altered_is_not_correct(bench, cpu, monkeypatch):
    to_records = train_wal.tree_to_records

    def altered(tree, chunk_elems):
        for i, (key, value) in enumerate(to_records(tree, chunk_elems)):
            if i == 1:
                value = value[:-1] + bytes([value[-1] ^ 1])
            yield key, value
    monkeypatch.setattr(train_wal, "tree_to_records", altered)
    out = _run(bench, cpu, "tiny_whisper.save10")
    assert not out["correct"]
    assert out["checks"]["store_mismatches"]["value"] > 0


def test_resumed_state_altered_is_not_correct(bench, cpu, monkeypatch):
    to_tree = train_wal.records_to_tree

    def altered(template, records, chunk_elems):
        tree = to_tree(template, records, chunk_elems)
        leaves, treedef = jax.tree.flatten(tree)
        leaves[0] = leaves[0].at[(0,) * leaves[0].ndim].add(1)
        return jax.tree.unflatten(treedef, leaves)
    monkeypatch.setattr(train_wal, "records_to_tree", altered)
    out = _run(bench, cpu, "tiny_whisper.resume")
    assert not out["correct"]
    assert out["checks"]["resumes_not_exact"]["value"] > 0
    assert out["failed"] > 0


def test_no_accelerator_exits_nonzero_with_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    root = R.CHECKOUT
    p = subprocess.run([sys.executable, str(root / "chipbench" / "run.py"),
                        "--workload", "whisper_base.save10", "--seed", "1",
                        "--seconds", "1", "--trace", "0"],
                       capture_output=True, text=True, env=env, cwd=root,
                       timeout=300)
    assert p.returncode != 0
    for line in p.stdout.splitlines():
        with pytest.raises(json.JSONDecodeError):
            json.loads(line)
    assert "accelerator" in p.stderr


def test_checks_compare_by_the_worst_leaf():
    from chipbench.reflib import compare
    ref_g = [np.full(4, 0.5), np.full(4, 1.0), np.full(4, 2.0),
             np.full(4, 5e-10)]
    prog_g = [np.full(4, 0.5), np.full(4, 1.1), np.full(4, 2.0),
              np.full(4, 2.5)]
    ref = {"loss": np.array([10.0, 9.0, 8.0]),
           "grad1": np.array([np.linalg.norm(x) for x in ref_g]),
           "grad1_leaves": ref_g,
           "update": np.array([1.0, 1.0, 1.0, 1.0])}
    prog = {"loss": np.array([10.0, 9.0, 8.08]),
            "grad1": np.array([np.linalg.norm(x) for x in prog_g]),
            "grad1_leaves": prog_g,
            "update": np.array([1.0, 1.0, 0.5, 1.0])}
    got = compare(prog, ref)
    assert got["loss_gap"] == pytest.approx(0.01)
    assert got["grad_gap"] == pytest.approx(0.1)      # leaf 4 left out
    assert got["grad_diff"] == pytest.approx(0.1)
    assert got["update_gap"] == pytest.approx(0.5)
    # a gradient of the same norm pointing elsewhere: only grad_diff sees it
    turned = [x.copy() for x in ref_g]
    turned[2] = np.array([2.0, 2.0, -2.0, -2.0])
    got = compare(dict(ref, grad1_leaves=turned), ref)
    assert got["grad_gap"] == 0.0
    assert got["grad_diff"] == pytest.approx(2 ** 0.5)
