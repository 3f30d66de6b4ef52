"""Distribution layer: sharding rules + lowering specs on a small host mesh.

Runs in a subprocess with 8 forced host devices so the main test process
keeps its single-device view (dryrun.py's 512-device trick, miniaturized).
"""
import json
import os
import subprocess
import sys
import textwrap

import pytest

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import dataclasses, json
    import jax
    from jax.sharding import PartitionSpec as P
    from repro.configs import get_config, TRAIN_4K, DECODE_32K
    from repro.launch.steps import make_spec
    from repro.parallel.sharding import param_pspec, set_layout
    from repro.models import build_model

    from jax.sharding import AxisType
    mesh = jax.make_mesh((2, 4), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
    out = {}

    # --- param rules (full config shapes, no allocation)
    cfg = get_config("qwen3-8b")
    api = build_model(cfg)
    shapes = jax.eval_shape(api.init, jax.random.PRNGKey(0))
    flat = jax.tree_util.tree_flatten_with_path(shapes)[0]
    specs = {"/".join(str(getattr(p, "key", p)) for p in path):
             str(param_pspec(path, a, mesh)) for path, a in flat}
    out["wq_spec"] = specs["blocks/attn/wq"]
    out["wo_spec"] = specs["blocks/attn/wo"]
    out["embed_spec"] = specs["embed"]
    out["norm_spec"] = specs["final_norm/scale"]

    # --- a reduced config actually lowers + compiles on the small mesh
    red = dataclasses.replace(
        get_config("llama3.2-3b").reduced(), n_kv_heads=4)
    shape = dataclasses.replace(TRAIN_4K, seq_len=64, global_batch=8)
    spec = make_spec(red, shape, mesh)
    with jax.set_mesh(mesh):
        compiled = jax.jit(spec.fn).lower(*spec.args).compile()
    out["train_compiles"] = True

    shape_d = dataclasses.replace(DECODE_32K, seq_len=128, global_batch=8)
    spec = make_spec(red, shape_d, mesh)
    with jax.set_mesh(mesh):
        compiled = jax.jit(spec.fn).lower(*spec.args).compile()
    out["decode_compiles"] = True

    # --- fsdp layout produces no TP on feature dims
    set_layout("fsdp")
    specs2 = {"/".join(str(getattr(p, "key", p)) for p in path):
              str(param_pspec(path, a, mesh)) for path, a in flat}
    out["wq_spec_fsdp"] = specs2["blocks/attn/wq"]
    print(json.dumps(out))
""")


@pytest.fixture(scope="module")
def subproc_out():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    r = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                       capture_output=True, text=True, timeout=420)
    assert r.returncode == 0, r.stderr[-3000:]
    return json.loads(r.stdout.strip().splitlines()[-1])


def test_tp_param_rules(subproc_out):
    o = subproc_out
    assert "'data', 'model'" in o["wq_spec"]          # col-parallel + FSDP
    assert "'model', 'data'" in o["wo_spec"]          # row-parallel + FSDP
    assert "'model'" in o["embed_spec"]               # vocab over model
    assert o["norm_spec"] == "PartitionSpec()"        # norms replicate


def test_fsdp_layout_has_no_tp(subproc_out):
    # storage-only sharding: exactly one sharded dim, on the big axis
    assert subproc_out["wq_spec_fsdp"].count("'model'") <= 1
    assert "PartitionSpec(None," in subproc_out["wq_spec_fsdp"]


def test_small_mesh_lower_compile(subproc_out):
    assert subproc_out["train_compiles"] and subproc_out["decode_compiles"]
