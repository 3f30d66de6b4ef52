"""The yardstick on the CPU: the FLOP count against the program's own
jaxpr, the peaks table, and the trace reduction on a recorded trace."""
import dataclasses
import json
from pathlib import Path

import jax
import pytest

from chipbench import flops, trace_reduce
from chipbench.peaks import peaks

DATA = Path(__file__).parent / "data"
CONFIGS = Path(__file__).parents[1] / "configs"


def _dot_flops(jaxpr, mult=1.0) -> float:
    """Matmul operations in a jaxpr, with scan bodies times their length."""
    total = 0.0
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name == "dot_general":
            lhs, rhs = (v.aval for v in eqn.invars[:2])
            (lc, _), (lb, _) = eqn.params["dimension_numbers"]
            k = b = 1
            for d in lc:
                k *= lhs.shape[d]
            for d in lb:
                b *= lhs.shape[d]
            m = lhs.size // (k * b)
            n = rhs.size // (k * b)
            total += mult * 2.0 * b * m * n * k
        elif name == "scan":
            total += _dot_flops(eqn.params["jaxpr"].jaxpr,
                                mult * eqn.params["length"])
        else:
            for p in eqn.params.values():
                inner = getattr(p, "jaxpr", None)
                if inner is not None and hasattr(inner, "eqns"):
                    total += _dot_flops(inner, mult)
                elif hasattr(p, "eqns"):
                    total += _dot_flops(p, mult)
    return total


@pytest.mark.parametrize("name", ["tiny_whisper", "tiny_qwen2"])
def test_flops_match_the_programs_forward_jaxpr(name):
    from repro.configs import get_config
    from repro.models import lm, whisper
    cfg = json.loads((DATA / f"{name}.json").read_text())
    prog = cfg["program"]
    pc = dataclasses.replace(get_config(prog["arch"]),
                             **{f: cfg[k] for f, k in prog["fields"].items()})
    job = cfg["job"]
    from repro.models import build_model
    from repro.models.api import make_batch
    params = jax.eval_shape(build_model(pc).init, jax.random.PRNGKey(0))
    batch = make_batch(pc, job["batch"], job["seq"])
    loss = whisper.loss_fn if cfg["model_type"] == "whisper" else lm.loss_fn
    jaxpr = jax.make_jaxpr(lambda p, b: loss(p, b, pc, remat=False))(
        params, batch)
    counted = _dot_flops(jaxpr.jaxpr)
    # the program masks a full score matrix, so count S^2 pairs here
    assert counted == pytest.approx(
        flops.forward_flops(cfg, job, causal="full"), rel=1e-9)
    assert flops.train_step_flops(cfg, job) < 3 * counted


def test_flops_causal_convention_counts_kept_pairs():
    cfg = json.loads((DATA / "tiny_qwen2.json").read_text())
    job = dict(cfg["job"], seq=8)
    full = flops.forward_flops(cfg, job, causal="full")
    kept = flops.forward_flops(cfg, job)
    d = cfg["num_attention_heads"] * cfg["assumed"]["head_dim"]
    per_pair = 2 * 2 * d * job["batch"] * cfg["num_hidden_layers"]
    assert full - kept == pytest.approx(per_pair * (64 - 36))


# train_step_flops as the parent computed it, before the counts moved to
# chipbench/counts/: a move that changes a digit changes every step_mfu
PARENT_STEP_FLOPS = {"whisper_base": 3521397129216.0,
                     "qwen2_5_3b_l1": 11812435132416.0}


@pytest.mark.parametrize("name", sorted(PARENT_STEP_FLOPS))
def test_train_step_flops_are_the_parents_to_the_last_digit(name):
    cfg = json.loads((CONFIGS / f"{name}.json").read_text())
    assert flops.train_step_flops(cfg, cfg["job"]) == PARENT_STEP_FLOPS[name]


def test_a_family_without_a_count_names_the_file_to_add():
    with pytest.raises(FileNotFoundError,
                       match="chipbench/counts/no_such_family.py"):
        flops.forward_flops({"model_type": "no_such_family"}, {})


def test_peaks_refuse_an_unknown_device_kind():
    assert peaks("TPU v5 lite")["bf16_flops"] == 197e12
    assert peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="no published peaks"):
        peaks("TPU v9 imaginary")


def test_trace_reduce_on_a_recorded_trace():
    """A trace recorded on one TPU v5e: three rounds of four jitted
    2048x2048 bf16 matmul steps under ``train_step``, each followed by a
    50 ms host sleep under ``wal.save``, then a 20 ms ``wal.heartbeat``."""
    pd = trace_reduce.load(str(DATA / "v5e_probe.xplane.pb"))
    red = trace_reduce.reduce(pd, ("train_step", "wal.save",
                                   "wal.heartbeat"))
    assert red["devices"] == 1
    assert 0 < red["busy_s"] < red["window_s"]
    assert red["window_s"] == pytest.approx(0.176679126)
    assert red["busy_s"] == pytest.approx(0.000820194, rel=1e-6)
    # the last save's sleep and the heartbeat's are one gap, cut in two
    gaps = red["idle_gaps"]
    assert [name for name, _ in gaps[:3]] == ["wal.save"] * 3
    assert all(0.049 < secs < 0.052 for _, secs in gaps[:3])
    assert gaps[3][0] == "wal.heartbeat"
    assert gaps[3][1] == pytest.approx(0.0207, abs=1e-3)
    assert [name for name, _ in red["device_ops"]] == [
        "fusion", "copy-done", "copy-start"]
    # the device clock reads 1.5 ms early here: the first round's four runs
    # fall before the window opens on the host's clock
    (name, runs), = red["modules"].items()
    assert name.startswith("jit__lambda(") and runs["count"] == 8


def test_trace_reduce_refuses_a_trace_without_a_window():
    pd = trace_reduce.load(str(DATA / "v5e_probe.xplane.pb"))
    with pytest.raises(ValueError, match="no 'nowhere' annotation"):
        trace_reduce.reduce(pd, ("train_step",), window="nowhere")
