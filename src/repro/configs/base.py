"""Architecture + shape configuration.

One ``ModelConfig`` covers all ten assigned architecture families; family-
specific fields are ignored by families that don't use them.  Configs are
frozen dataclasses so they hash (usable as jit static args).
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field


@dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                    # dense | ssm | hybrid | moe | vlm | audio
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab_size: int

    head_dim: int = 0              # 0 -> d_model // n_heads
    norm: str = "rmsnorm"          # rmsnorm | layernorm
    act: str = "swiglu"            # swiglu | gelu
    qk_norm: bool = False
    qkv_bias: bool = False
    partial_rotary: float = 1.0    # fraction of head_dim that rotates
    rope_theta: float = 1_000_000.0
    tie_embeddings: bool = False

    # --- MoE
    n_experts: int = 0
    top_k: int = 0
    moe_d_ff: int = 0              # per-expert hidden
    n_shared_experts: int = 0
    first_dense_layers: int = 0    # leading layers use dense FFN
    capacity_factor: float = 1.25

    # --- RWKV6
    rwkv_head_dim: int = 64

    # --- Mamba2 / hybrid
    ssm_state: int = 0
    ssm_expand: int = 2
    ssm_headdim: int = 64
    ssm_conv: int = 4
    attn_every: int = 0            # zamba2: shared attn block period (0=never)

    # --- enc-dec (whisper)
    n_enc_layers: int = 0
    enc_ctx: int = 1500            # audio frame positions (stub frontend)

    # --- vlm (pixtral)
    n_patches: int = 0             # image patch embeddings prepended (stub)

    max_seq: int = 532_000
    dtype: str = "bfloat16"
    remat: bool = True             # activation checkpointing in train loss

    @property
    def hd(self) -> int:
        return self.head_dim or self.d_model // self.n_heads

    @property
    def attention_free(self) -> bool:
        return self.family == "ssm"

    @property
    def is_enc_dec(self) -> bool:
        return self.n_enc_layers > 0

    def n_params(self) -> int:
        """Total parameter count (exact, mirrors init fns)."""
        D, F, V, L = self.d_model, self.d_ff, self.vocab_size, self.n_layers
        H, KV, hd = self.n_heads, self.n_kv_heads, self.hd
        emb = V * D * (1 if self.tie_embeddings else 2)
        total = emb

        def attn_p() -> int:
            p = D * H * hd + 2 * D * KV * hd + H * hd * D
            if self.qkv_bias:
                p += (H + 2 * KV) * hd
            if self.qk_norm:
                p += 2 * hd
            return p

        def dense_ffn(f: int) -> int:
            return D * f * (3 if self.act == "swiglu" else 2)

        def mamba_p() -> int:
            din = self.ssm_expand * D
            nh = din // self.ssm_headdim
            inp = D * (2 * din + 2 * self.ssm_state + nh)
            conv = (din + 2 * self.ssm_state) * self.ssm_conv
            out = din * D
            extra = nh * 2 + din          # A, D, dt_bias + norm
            return inp + conv + out + extra

        if self.family in ("dense", "vlm"):
            total += L * (attn_p() + dense_ffn(F) + 2 * D)
        elif self.family == "moe":
            moe_f = self.moe_d_ff or F
            per_moe = (D * self.n_experts                      # router
                       + self.n_experts * D * moe_f * 3
                       + self.n_shared_experts * D * moe_f * 3)
            n_moe = L - self.first_dense_layers
            total += L * (attn_p() + 2 * D)
            total += n_moe * per_moe + self.first_dense_layers * dense_ffn(F)
        elif self.family == "ssm":                              # rwkv6
            hdw = self.rwkv_head_dim
            nh = D // hdw
            tmix = 6 * D + D * D * 4 + nh * hdw + D * 64 * 2 + 64 * D  # r,k,v,o,w-lora,u
            cmix = 2 * D + D * F + F * D
            total += L * (tmix + cmix + 2 * D)
        elif self.family == "hybrid":                           # zamba2
            total += L * (mamba_p() + 2 * D)
            total += attn_p() + dense_ffn(F) + 2 * D            # one shared block
        elif self.family == "audio":                            # whisper enc-dec
            enc = self.n_enc_layers * (attn_p() + dense_ffn(F) + 2 * D)
            dec = L * (2 * attn_p() + dense_ffn(F) + 3 * D)     # self+cross attn
            total += enc + dec + self.enc_ctx * D               # enc pos-embed
        total += D                                              # final norm
        return total

    def n_active_params(self) -> int:
        """Per-token active params (MoE: top_k + shared experts only)."""
        if self.family != "moe":
            return self.n_params()
        moe_f = self.moe_d_ff or self.d_ff
        per_tok_moe = (self.top_k + self.n_shared_experts) * self.d_model * moe_f * 3
        n_moe = self.n_layers - self.first_dense_layers
        all_moe = (self.n_experts + self.n_shared_experts) * self.d_model * moe_f * 3
        return self.n_params() - n_moe * (all_moe - per_tok_moe)

    def reduced(self) -> "ModelConfig":
        """Tiny same-family config for CPU smoke tests."""
        return dataclasses.replace(
            self,
            name=self.name + "-smoke",
            n_layers=max(2, self.attn_every or 0, self.first_dense_layers + 1),
            d_model=128,
            n_heads=4,
            n_kv_heads=min(4, max(1, self.n_kv_heads * 4 // self.n_heads)),
            d_ff=256,
            head_dim=32,
            vocab_size=512,
            n_experts=min(self.n_experts, 4),
            top_k=min(self.top_k, 2),
            moe_d_ff=64 if self.n_experts else 0,
            ssm_state=min(self.ssm_state, 16),
            ssm_headdim=32 if self.ssm_state else 64,
            rwkv_head_dim=32,
            n_enc_layers=2 if self.n_enc_layers else 0,
            enc_ctx=16,
            n_patches=8 if self.n_patches else 0,
            attn_every=3 if self.attn_every else 0,
            max_seq=256,
        )


@dataclass(frozen=True)
class ShapeConfig:
    name: str
    seq_len: int
    global_batch: int
    kind: str          # train | prefill | decode


TRAIN_4K = ShapeConfig("train_4k", 4_096, 256, "train")
DECODE_32K = ShapeConfig("decode_32k", 32_768, 128, "decode")
