"""Plain reference of the qwen2_5_3b_l1 configuration: a Qwen2 decoder as
``qwen2_5_3b_l1.json`` states it (RMSNorm, grouped-query attention with QKV
bias and rotary positions, SwiGLU, tied embedding), in float32.

Weights are the pytree the benchmark made from the seed: ``embed``,
``blocks`` stacked over layers, ``final_norm``.
"""
import jax.numpy as jnp

from chipbench.reflib import attention, cross_entropy, rms_norm, silu


def loss(params, batch, cfg, mm):
    eps = cfg["rms_norm_eps"]
    emb = params["embed"].astype(jnp.float32)
    x = emb[batch["tokens"]]
    blocks = params["blocks"]
    for i in range(cfg["num_hidden_layers"]):
        p = {k: {n: w[i] for n, w in v.items()} for k, v in blocks.items()}
        h = rms_norm(x, p["ln1"], eps)
        x = x + attention(h, h, p["attn"], mm,
                          heads=cfg["num_attention_heads"],
                          kv_heads=cfg["num_key_value_heads"], causal=True,
                          theta=cfg["rope_theta"])
        h = rms_norm(x, p["ln2"], eps)
        m = p["mlp"]
        g = silu(mm("bsd,df->bsf", h, m["wg"])) * mm("bsd,df->bsf", h, m["wu"])
        x = x + mm("bsf,fd->bsd", g, m["wd"])
    x = rms_norm(x, params["final_norm"], eps)
    return cross_entropy(mm("bsd,vd->bsv", x, emb), batch["tokens"])
