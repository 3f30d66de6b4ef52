"""Forward model operations of one Whisper-style encoder-decoder step: the
encoder over its frames; the decoder over its tokens, with cross-attention
K/V projected over the frames and scores over tokens × frames; the tied
output head over every decoder position."""
from chipbench.flops import _attn, _pairs


def forward(c: dict, job: dict, causal: str = "mask") -> float:
    batch, seq, frames = job["batch"], job["seq"], job["frames"]
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
