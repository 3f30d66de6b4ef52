"""Forward model operations of one step of a dense GQA decoder (Qwen2):
attention with grouped K/V heads, a SwiGLU MLP, the output head."""
from chipbench.flops import _attn, _pairs


def forward(c: dict, job: dict, causal: str = "mask") -> float:
    batch, seq = job["batch"], job["seq"]
    d, v = c["hidden_size"], c["vocab_size"]
    hd = c.get("head_dim") or d // c["num_attention_heads"]
    qw = c["num_attention_heads"] * hd
    kvw = c["num_key_value_heads"] * hd
    layer = (_attn(seq, seq, d, qw, kvw, _pairs(seq, causal), qw)
             + 3 * 2 * seq * d * c["intermediate_size"])          # SwiGLU
    head = 2 * seq * d * v
    return batch * (c["num_hidden_layers"] * layer + head)
