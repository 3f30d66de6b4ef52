"""Shared set-up for the benchmark's own tests, which run on the CPU.

``bench`` is ``BENCHMARK.json`` with two tiny configurations added
(``tests/data/tiny_*.json``, their limits set from CPU readings at that
size) and a cell of each under every mix; ``cpu`` stands in for the chips
``require_chips`` would give, since the harness refuses the CPU.
"""
import copy
import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

TINY = ("tiny_whisper", "tiny_qwen2")
MIXES = ("save10", "resume", "nolog")


def tiny_bench() -> dict:
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    for c in TINY:
        bench["configs"].append({"name": c, "file":
                                 f"chipbench/tests/data/{c}.json"})
        for m in MIXES:
            bench["workloads"].append({"name": f"{c}.{m}", "config": c,
                                       "traffic": m, "chips": 1})
    for metric in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in metric:
            mixes = {w.split(".", 1)[1] for w in metric["workloads"]}
            if "save10" in mixes:       # the training loop without a log
                mixes.add("nolog")
            metric["workloads"] += [f"{c}.{m}" for c in TINY for m in mixes]
    return bench


@pytest.fixture(scope="session")
def bench():
    return tiny_bench()


@pytest.fixture
def fresh_bench(bench):
    return copy.deepcopy(bench)


@pytest.fixture(scope="session")
def cpu():
    import jax

    from chipbench.peaks import peaks
    return jax.devices()[:1], peaks("TPU v5 lite")
