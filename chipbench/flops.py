"""Model operations of one training step, counted matmul by matmul from the
shapes in a configuration's JSON file.

Each family's count is a file of its own, ``chipbench/counts/<model_type>.py``,
found by the configuration's ``model_type`` as a per-layer metric's reader
is found by its name.  It holds ``forward(c, job, causal="mask")``: the
forward operations of one step of ``job``, built from the helpers here.

Conventions:

- A matmul of (m, k) by (k, n) is 2·m·k·n operations.  Norms, softmax,
  activations and the optimizer are left out: next to the matmuls they are
  under one percent at these widths.
- A training step is three forward passes: the backward pass takes two
  matmuls of each forward one.  Recomputation under remat is not counted.
- Causal attention counts the S·(S+1)/2 query-key pairs a causal mask keeps,
  for scores and for values alike.  ``causal="full"`` counts S² pairs, which
  is what a program that masks a full score matrix computes; the test
  checks that form against the program's jaxpr.
"""
from __future__ import annotations

import importlib.util
from pathlib import Path

COUNTS = Path(__file__).resolve().parent / "counts"


def _pairs(s: int, causal: str) -> float:
    return s * s if causal == "full" else s * (s + 1) / 2


def _attn(tokens: int, kv_tokens: int, d: int, q_width: int, kv_width: int,
          pairs: float, heads_width: int) -> float:
    """Projections, scores and values, output projection of one layer."""
    return (2 * tokens * d * q_width            # q
            + 2 * 2 * kv_tokens * d * kv_width   # k, v
            + 2 * 2 * pairs * heads_width        # scores and values
            + 2 * tokens * q_width * d)          # output


def forward_flops(c: dict, job: dict, causal: str = "mask") -> float:
    family = c["model_type"]
    path = COUNTS / f"{family}.py"
    if not path.is_file():
        raise FileNotFoundError(
            f"no operation count for model_type {family!r}: add "
            f"chipbench/counts/{family}.py with forward(c, job, causal)")
    spec = importlib.util.spec_from_file_location(
        f"chipbench_count_{family}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.forward(c, job, causal)


def train_step_flops(c: dict, job: dict) -> float:
    return 3.0 * forward_flops(c, job)
