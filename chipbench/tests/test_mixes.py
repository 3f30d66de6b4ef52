"""Each mix's window run end to end at a tiny size on the CPU, through the
same program calls a chip run makes (``require_chips`` is the only step
left out)."""
import math

import pytest

from chipbench import run as R


@pytest.mark.parametrize("workload", ["tiny_whisper.save10",
                                      "tiny_whisper.resume",
                                      "tiny_whisper.nolog",
                                      "tiny_qwen2.save10"])
def test_mix_window_end_to_end(bench, cpu, workload):
    out = R.run_cell(bench, workload, 2**33 + 11, 0.2, False, chips=cpu)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert list(out)[-1] == "checks"
    names = set(out["metrics"])
    assert "setup_s" in names
    assert names & {"train_tokens_per_s", "resume_s"}
    for m in out["metrics"].values():
        assert math.isfinite(m["value"]) and m["value"] > 0
    assert out["device"]["count"] == 1


def test_periods_window_is_whole_periods(bench, cpu):
    out = R.run_cell(bench, "tiny_whisper.save10", 5, 0.0, False, chips=cpu)
    assert out["counts"]["steps"] == 20           # at least one period
    assert out["counts"]["final_step"] % 10 == 0  # ends at a save


def test_resume_window_runs_at_least_one_resume(bench, cpu):
    out = R.run_cell(bench, "tiny_whisper.resume", 6, 0.0, False, chips=cpu)
    assert out["counts"]["resumes"] == 1
    assert out["checks"]["resumes_not_exact"]["value"] == 0


def test_same_seed_same_inputs_and_weights():
    import jax
    import jax.numpy as jnp

    from chipbench import traffic
    shapes = {"embed": jax.ShapeDtypeStruct((16, 8), jnp.bfloat16),
              "w": jax.ShapeDtypeStruct((8, 4), jnp.bfloat16)}
    seed = 2**40 + 3                       # past 32 bits, as the driver's
    a, b = traffic.make_params(seed, shapes), traffic.make_params(seed, shapes)
    c = traffic.make_params(seed + 1, shapes)
    assert all(jnp.array_equal(x, y) for x, y in
               zip(jax.tree.leaves(a), jax.tree.leaves(b)))
    assert not jnp.array_equal(a["w"], c["w"])
    job = {"batch": 2, "seq": 8, "frames": 4}
    f, g = (traffic.make_feed(seed, job, 100, 8) for _ in range(2))
    assert jnp.array_equal(f(3)["tokens"], g(3)["tokens"])
    assert not jnp.array_equal(f(3)["tokens"], f(4)["tokens"])
    t = f(0)["tokens"]
    assert len({tuple(r) for r in t.tolist()}) == 2       # rows differ


def _old_rule_params(seed: int, shapes):
    """``traffic.make_params`` as it was before the ``init`` map: the
    reference the shared rules are held to, bit for bit."""
    import math

    import jax
    import jax.numpy as jnp

    from chipbench.traffic import leaf_name, seed_key

    def init_leaf(key, name, shape, dtype):
        last = name.rsplit("/", 1)[-1]
        if last == "scale":
            return jnp.ones(shape, dtype)
        if last in ("bias", "bq", "bk", "bv"):
            return jnp.zeros(shape, dtype)
        if last == "embed" or name == "embed":
            std = 0.02
        else:
            std = 1.0 / math.sqrt(shape[-2])
        return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)

    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    spec = [(leaf_name(p), tuple(s.shape), s.dtype) for p, s in leaves]

    def build(key):
        key = jax.random.fold_in(key, 0)
        return jax.tree_util.tree_unflatten(treedef, [
            init_leaf(jax.random.fold_in(key, i), n, sh, dt)
            for i, (n, sh, dt) in enumerate(spec)])

    return jax.jit(build)(seed_key(seed))


@pytest.mark.parametrize("name", ["tiny_whisper", "tiny_qwen2"])
def test_weights_are_the_old_rules_bit_for_bit(bench, name):
    import jax
    import jax.numpy as jnp

    from chipbench import traffic
    cfg = R.find_cell(bench, f"{name}.save10")[1]
    assert "init" not in cfg
    shapes = R.build_trainer(cfg, 0).shapes
    seed = 2**36 + 5
    new = traffic.make_params(seed, shapes, cfg.get("init"))
    old = _old_rule_params(seed, shapes)
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(new)[0],
                            jax.tree.leaves(old)):
        assert a.dtype == b.dtype and jnp.array_equal(a, b), path


def test_init_entry_of_another_form_is_refused():
    import jax
    import jax.numpy as jnp

    from chipbench import traffic
    shapes = {"w": jax.ShapeDtypeStruct((4, 4), jnp.float32)}
    with pytest.raises(ValueError, match="'w'"):
        traffic.make_params(1, shapes, {"w": "uniform"})
