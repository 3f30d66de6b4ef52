"""Fault-tolerant training on one TPU chip, end to end.

    python chip_smoke.py

Runs ``repro.launch.train.run`` -- the code behind
``python -m repro.launch.train --arch whisper-base --preset full`` -- at
whisper-base's published widths and depth, with random weights from a seed:
train, log the state through ``TrainWAL`` at step 0 and once more, log a
heartbeat every step, hard-crash mid-interval, recover, replay the tail,
check the restored state against the pre-crash state bit for bit, and train
one more step.

Every phase runs in this one process, which holds the chip.  The lines
before the last report host walls (not device metrics), peak device memory
and peak host RSS.  The last line is one JSON object; it is printed only when
every phase passed on a TPU.  Without a TPU the script exits 1 and prints no
result.
"""
from __future__ import annotations

import json
import resource
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

# whisper-base at its published size; batch 8 x 448 decoder tokens x 1500
# encoder frames fits one v5e's 16 GB (tests/test_tpu_compile.py)
SMOKE = dict(arch="whisper-base", preset="full", batch=8, seq=448, steps=5,
             crash_at=4, chunk_interval=3, ckpt_interval=25, log_every=1)


def main() -> int:
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found {dev.platform!r}",
              file=sys.stderr)
        return 1
    from repro.launch.compile_cache import use_compile_cache
    from repro.launch.train import run

    cache = Path(use_compile_cache())
    warm = len(list(cache.iterdir())) if cache.is_dir() else 0
    print(f"compile cache: {cache} ({warm} entries at start)")
    run(**SMOKE)
    peak_hbm = dev.memory_stats()["peak_bytes_in_use"]
    print(f"peak HBM (device memory_stats): {peak_hbm} bytes")
    rss = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024
    print(f"peak host RSS: {rss} bytes")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
