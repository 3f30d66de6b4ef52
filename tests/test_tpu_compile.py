"""Compile for a described TPU v5e chip, with no chip attached.

The TPU compiler is installed with JAX and compiles for a topology that is
only described.  That catches what the CPU cannot: programs the TPU compiler
refuses or that do not fit the chip's memory.  Nothing runs, so nothing here
says anything about results or times.

The topology is described inside a module fixture, never at import: only one
process at a time may load the TPU library.
"""
import importlib.util
import os
from pathlib import Path

import jax
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.data import TokenPipeline
from repro.launch.train import make_train_step, preset_config
from repro.models import build_model
from repro.optim import AdamWConfig, init_opt_state

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
