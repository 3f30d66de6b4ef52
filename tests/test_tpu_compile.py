"""Compile for a described TPU v5e chip, with no chip attached.

The TPU compiler is installed with JAX and compiles for a topology that is
only described.  That catches what interpret mode cannot: block shapes the
tiling refuses, primitives Mosaic cannot lower, programs that do not fit the
chip's memory.  Nothing runs, so nothing here says anything about results
or times.

The topology is described inside a module fixture, never at import: only one
process at a time may load the TPU library.
"""
import importlib.util
import os
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.data import TokenPipeline
from repro.kernels.delta_apply import delta_apply
from repro.kernels.flash_attention import flash_attention
from repro.kernels.ssd_scan import ssd_scan
from repro.kernels.wkv6 import wkv6
from repro.launch.train import make_train_step, preset_config
from repro.models import build_model
from repro.optim import AdamWConfig, init_opt_state
from repro.state_store import WALConfig

V5E_HBM_BYTES = 15.75e9      # what the compiler lets one program use


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _shape(one_chip, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


def test_flash_attention_compiles(one_chip):
    # qwen2.5-3b heads: 16 query heads over 2 KV heads of 128
    q = _shape(one_chip, (4, 16, 2048, 128), jnp.bfloat16)
    kv = _shape(one_chip, (4, 2, 2048, 128), jnp.bfloat16)
    fn = jax.jit(lambda q, k, v: flash_attention(q, k, v, causal=True))
    _assert_kernel(fn.lower(q, kv, kv).compile())


def test_delta_apply_compiles(one_chip):
    # TrainWAL's fp32 chunk is one slot wide
    width = WALConfig.chunk_elems
    n_pages, slots, max_upd = 16, 8, 8
    pages = _shape(one_chip, (n_pages, slots, width), jnp.float32)
    vals = _shape(one_chip, (n_pages, max_upd, width), jnp.float32)
    slot_idx = _shape(one_chip, (n_pages, max_upd), jnp.int32)
    mask = _shape(one_chip, (n_pages, max_upd), jnp.bool_)
    for additive in (False, True):
        fn = jax.jit(lambda p, v, s, m: delta_apply(p, v, s, m,
                                                    additive=additive))
        _assert_kernel(fn.lower(pages, vals, slot_idx, mask).compile())


def test_ssd_scan_compiles(one_chip):
    # zamba2-2.7b: 80 heads of 64, state 64
    cfg = get_config("zamba2-2.7b")
    H = cfg.ssm_expand * cfg.d_model // cfg.ssm_headdim
    P, N, T = cfg.ssm_headdim, cfg.ssm_state, 1024
    assert (H, P, N) == (80, 64, 64)
    x = _shape(one_chip, (1, H, T, P), jnp.bfloat16)
    dt = _shape(one_chip, (1, H, T), jnp.bfloat16)
    bc = _shape(one_chip, (1, T, N), jnp.bfloat16)
    A = _shape(one_chip, (H,), jnp.float32)
    fn = jax.jit(lambda x, dt, b, c, a: ssd_scan(x, dt, b, c, a))
    _assert_kernel(fn.lower(x, dt, bc, bc, A).compile())


def test_wkv6_compiles(one_chip):
    # rwkv6-3b: 40 heads of 64
    cfg = get_config("rwkv6-3b")
    H, hd, T = cfg.d_model // cfg.rwkv_head_dim, cfg.rwkv_head_dim, 1024
    assert (H, hd) == (40, 64)
    x = _shape(one_chip, (1, H, T, hd), jnp.bfloat16)
    u = _shape(one_chip, (H, hd), jnp.float32)
    fn = jax.jit(lambda r, k, v, w, u: wkv6(r, k, v, w, u))
    _assert_kernel(fn.lower(x, x, x, x, u).compile())


def _smoke_settings() -> dict:
    path = Path(__file__).resolve().parents[1] / "chip_smoke.py"
    spec = importlib.util.spec_from_file_location("chip_smoke", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.SMOKE


def test_smoke_train_step_fits_one_chip(one_chip):
    """chip_smoke.py's train step: whisper-base at its published size."""
    s = _smoke_settings()
    cfg = preset_config(get_config(s["arch"]), s["preset"])
    api = build_model(cfg)

    def init_state():
        params = api.init(jax.random.PRNGKey(0))
        return {"params": params, "opt": init_opt_state(params)}

    pipe = TokenPipeline(cfg, s["batch"], s["seq"])
    place = lambda a: _shape(one_chip, a.shape, a.dtype)
    state = jax.tree.map(place, jax.eval_shape(init_state))
    batch = jax.tree.map(place, jax.eval_shape(lambda: pipe.batch_at(0)))
    assert batch["frames"].shape == (8, 1500, 512)
    step = make_train_step(api, AdamWConfig(total_steps=s["steps"]))
    mem = step.lower(state, batch).compile().memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert used <= V5E_HBM_BYTES, mem
