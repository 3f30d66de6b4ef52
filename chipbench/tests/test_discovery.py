"""A later change adds a mix, a configuration, a model family's operation
count or a per-layer metric by adding files and entries: the harness finds
them by name, and no file that is already there changes."""
import json
import shutil

import pytest

from chipbench import flops, traffic
from chipbench import run as R


@pytest.fixture
def copy_of_chipbench(tmp_path, monkeypatch):
    root = tmp_path / "checkout"
    shutil.copytree(R.HERE, root / "chipbench",
                    ignore=shutil.ignore_patterns("out", "__pycache__"))
    before = {p: p.read_bytes() for p in (root / "chipbench").rglob("*")
              if p.is_file()}
    monkeypatch.setattr(R, "HERE", root / "chipbench")
    monkeypatch.setattr(flops, "COUNTS", root / "chipbench" / "counts")
    yield root / "chipbench"
    for p, data in before.items():
        assert p.read_bytes() == data, f"{p} changed"


def test_new_mix_and_metric_found_by_name(copy_of_chipbench, fresh_bench,
                                          cpu):
    here = copy_of_chipbench
    (here / "mixes" / "save5.json").write_text(
        '{"kind": "periods", "log": true, "save_every": 5, '
        '"checkpoint_every": 10, "bg_flush_pages": 32, '
        '"pool_pages_per_record": 2, "first_steps": 5, "period_steps": 10}')
    (here / "layer_metrics" / "saves_per_period.py").write_text(
        "def read(run):\n"
        "    t = run.spans.get('wal.save')\n"
        "    return float(len(t)) if t else None\n")
    cell = {"name": "tiny_whisper.save5", "config": "tiny_whisper",
            "traffic": "save5", "chips": 1}
    fresh_bench["workloads"].append(cell)
    fresh_bench["per_layer"].append(
        {"name": "saves_per_period", "unit": "saves", "better": "lower",
         "source": "program_span", "layer": "save", "moves":
         "train_tokens_per_s", "workloads": ["tiny_whisper.save5"]})
    fresh_bench["end_to_end"][0]["workloads"].append("tiny_whisper.save5")
    _, _, mix = R.find_cell(fresh_bench, "tiny_whisper.save5")
    assert mix["save_every"] == 5
    chosen = [m["name"] for m in R.cell_metrics(fresh_bench, cell, True)]
    assert "saves_per_period" in chosen
    out = R.run_cell(fresh_bench, "tiny_whisper.save5", 9, 0.0, False,
                     chips=cpu)
    assert out["correct"], out["checks"]
    assert out["counts"]["steps"] == 10
    assert "train_tokens_per_s" in out["metrics"]


def test_new_family_count_and_init_found_by_name(copy_of_chipbench):
    import jax
    import jax.numpy as jnp
    here = copy_of_chipbench
    (here / "counts" / "toy_family.py").write_text(
        "def forward(c, job, causal='mask'):\n"
        "    return 2.0 * job['batch'] * job['seq'] * c['hidden_size'] ** 2\n")
    (here / "configs" / "toy.json").write_text(json.dumps(
        {"name": "toy", "model_type": "toy_family", "hidden_size": 8,
         "job": {"batch": 2, "seq": 4},
         "init": {"router/score_bias": "ones", "gate": {"normal": 0.5}}}))
    cfg = json.loads((here / "configs" / "toy.json").read_text())
    assert flops.train_step_flops(cfg, cfg["job"]) == 3.0 * 2.0 * 2 * 4 * 64
    shapes = {"router": {"score_bias": jax.ShapeDtypeStruct((8,), jnp.float32),
                         "w": jax.ShapeDtypeStruct((8, 8), jnp.bfloat16)},
              "gate": jax.ShapeDtypeStruct((), jnp.float32)}
    seed = 2**35 + 7
    params = traffic.make_params(seed, shapes, cfg["init"])
    assert params["router"]["score_bias"].dtype == jnp.float32
    assert jnp.array_equal(params["router"]["score_bias"], jnp.ones(8))
    # leaves in flattening order: gate, router/score_bias, router/w
    key = jax.random.fold_in(jax.random.fold_in(traffic.seed_key(seed), 0), 0)
    assert jnp.array_equal(params["gate"],
                           jax.random.normal(key, (), jnp.float32) * 0.5)
    with pytest.raises(ValueError, match="router/score_bias"):
        traffic.make_params(seed, shapes, {"gate": "zeros"})


def test_metric_reader_that_finds_nothing_is_left_out(copy_of_chipbench):
    (copy_of_chipbench / "layer_metrics" / "nothing_here.py").write_text(
        "def read(run):\n    return None\n")
    assert R.read_layer_metric("nothing_here", object()) is None


def test_unknown_workload_is_refused(fresh_bench):
    with pytest.raises(SystemExit):
        R.find_cell(fresh_bench, "no_such.cell")
