"""Plain reference of the whisper_base configuration: the encoder-decoder
as ``whisper_base.json`` states it, in float32, one layer after another.

Weights are the pytree the benchmark made from the seed: ``embed`` (tied
head), ``enc_blocks`` and ``dec_blocks`` stacked over layers, and the final
norms.
"""
import jax.numpy as jnp

from chipbench.reflib import (attention, cross_entropy, gelu_tanh,
                              layer_norm)


def sinusoids(length: int, d: int):
    pos = jnp.arange(length, dtype=jnp.float32)[:, None]
    dim = jnp.arange(0, d, 2, dtype=jnp.float32)[None, :]
    ang = pos / 10_000.0 ** (dim / d)
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


def loss(params, batch, cfg, mm):
    eps = cfg["assumed"]["layer_norm_eps"]
    theta = cfg["assumed"]["rope_theta"]
    heads = cfg["encoder_attention_heads"]
    x = batch["frames"].astype(jnp.float32)
    x = x + sinusoids(x.shape[1], x.shape[2])

    def mlp(h, p):
        return mm("bsf,fd->bsd", gelu_tanh(mm("bsd,df->bsf", h, p["wu"])),
                  p["wd"])

    enc = params["enc_blocks"]
    for i in range(cfg["encoder_layers"]):
        p = {k: {n: w[i] for n, w in v.items()} for k, v in enc.items()}
        h = layer_norm(x, p["ln1"], eps)
        x = x + attention(h, h, p["attn"], mm, heads=heads, kv_heads=heads,
                          causal=False, theta=theta)
        x = x + mlp(layer_norm(x, p["ln2"], eps), p["mlp"])
    enc_out = layer_norm(x, params["enc_norm"], eps)

    heads = cfg["decoder_attention_heads"]
    emb = params["embed"].astype(jnp.float32)
    y = emb[batch["tokens"]]
    dec = params["dec_blocks"]
    for i in range(cfg["decoder_layers"]):
        p = {k: {n: w[i] for n, w in v.items()} for k, v in dec.items()}
        h = layer_norm(y, p["ln1"], eps)
        y = y + attention(h, h, p["self_attn"], mm, heads=heads,
                          kv_heads=heads, causal=True, theta=theta)
        h = layer_norm(y, p["ln_x"], eps)
        y = y + attention(h, enc_out, p["cross_attn"], mm, heads=heads,
                          kv_heads=heads, causal=False, theta=None)
        y = y + mlp(layer_norm(y, p["ln2"], eps), p["mlp"])
    y = layer_norm(y, params["dec_norm"], eps)
    return cross_entropy(mm("bsd,vd->bsv", y, emb), batch["tokens"])
