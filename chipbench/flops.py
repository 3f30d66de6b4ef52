"""Model operations of one training step, counted matmul by matmul from the
shapes in a configuration's JSON file.

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
- Encoder-decoder: the encoder over its frames; the decoder over its tokens,
  with cross-attention K/V projected over the frames and scores over
  tokens × frames; the tied output head over every decoder position.
"""
from __future__ import annotations


def _pairs(s: int, causal: str) -> float:
    return s * s if causal == "full" else s * (s + 1) / 2


def _attn(tokens: int, kv_tokens: int, d: int, q_width: int, kv_width: int,
          pairs: float, heads_width: int) -> float:
    """Projections, scores and values, output projection of one layer."""
    return (2 * tokens * d * q_width            # q
            + 2 * 2 * kv_tokens * d * kv_width   # k, v
            + 2 * 2 * pairs * heads_width        # scores and values
            + 2 * tokens * q_width * d)          # output


def forward_whisper(c: dict, batch: int, seq: int, frames: int,
                    causal: str = "mask") -> float:
    d, v = c["d_model"], c["vocab_size"]
    enc = c["encoder_layers"] * (
        _attn(frames, frames, d, d, d, frames * frames, d)
        + 2 * 2 * frames * d * c["encoder_ffn_dim"])
    dec = c["decoder_layers"] * (
        _attn(seq, seq, d, d, d, _pairs(seq, causal), d)          # self
        + _attn(seq, frames, d, d, d, seq * frames, d)            # cross
        + 2 * 2 * seq * d * c["decoder_ffn_dim"])
    head = 2 * seq * d * v
    return batch * (enc + dec + head)


def forward_qwen2(c: dict, batch: int, seq: int, causal: str = "mask"
                  ) -> float:
    d, v = c["hidden_size"], c["vocab_size"]
    hd = c.get("head_dim") or d // c["num_attention_heads"]
    qw = c["num_attention_heads"] * hd
    kvw = c["num_key_value_heads"] * hd
    layer = (_attn(seq, seq, d, qw, kvw, _pairs(seq, causal), qw)
             + 3 * 2 * seq * d * c["intermediate_size"])          # SwiGLU
    head = 2 * seq * d * v
    return batch * (c["num_hidden_layers"] * layer + head)


FORWARD = {"whisper": forward_whisper, "qwen2": forward_qwen2}


def forward_flops(c: dict, job: dict, causal: str = "mask") -> float:
    fn = FORWARD[c["model_type"]]
    if c["model_type"] == "whisper":
        return fn(c, job["batch"], job["seq"], job["frames"], causal)
    return fn(c, job["batch"], job["seq"], causal)


def train_step_flops(c: dict, job: dict) -> float:
    return 3.0 * forward_flops(c, job)
