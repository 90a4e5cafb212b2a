"""Plain float32 dense GQA language model (Granite's block), from the
configuration file alone.

Each layer, with h the residual stream:
  h = h + residual_multiplier * attn(rms_norm(h)),
  h = h + residual_multiplier * mlp(rms_norm(h)),
where attn projects q (num_attention_heads heads), k and v
(num_key_value_heads heads, each shared by a group of q heads), turns q and
k by rotary embeddings (rope_theta, the two halves of each head rotated
against each other), takes causal softmax attention with scores scaled by
``attention_multiplier``, and projects out; mlp is down(silu(gate(x)) *
up(x)). The embedding is scaled by ``embedding_multiplier``, the logits
(the tied embedding as head) divided by ``logits_scaling``, and the loss
has a z-loss term. The configuration file states the multipliers the
program uses, which are not the published ones (its ``reduced``).

The parameters come in the layout of the benchmark's weights
(``weights.py``): layers stacked along a leading axis under blocks/sb/slot0,
attention under "attn" (wq, wk, wv, wo), the MLP under "mlp" (wi up, wg
gate, wo down).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench.reflib import layer_stack, rms_norm, silu, token_loss

LAYER_GROUP = 5          # layers whose inputs are kept while one is recomputed


def rotary(x, theta):
    """x (B, S, H, hd): position s turns the pair (x[i], x[i + hd/2]) by
    s * theta^(-2i/hd)."""
    s, half = x.shape[1], x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freq       # (S, half)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(p, x, conf, num):
    b, s, _ = x.shape
    H, KV = conf["num_attention_heads"], conf["num_key_value_heads"]
    hd = conf["head_dim"]
    q = rotary(num.mm("bsd,dhk->bshk", x, p["wq"]), conf["rope_theta"])
    k = rotary(num.mm("bsd,dhk->bshk", x, p["wk"]), conf["rope_theta"])
    v = num.mm("bsd,dhk->bshk", x, p["wv"])
    q = q.reshape(b, s, KV, H // KV, hd)
    causal = jnp.tril(jnp.ones((s, s), bool))

    @jax.checkpoint
    def one(args):                      # one kv head and its group of q heads
        qh, kh, vh = args               # (B, S, G, hd) (B, S, hd) (B, S, hd)
        sc = num.mm("bqgh,bkh->bgqk", qh, kh) * conf["attention_multiplier"]
        w = jax.nn.softmax(jnp.where(causal, sc, -jnp.inf), axis=-1)
        return num.mm("bgqk,bkh->bqgh", w, vh)

    o = jax.lax.map(one, (jnp.moveaxis(q, 2, 0), jnp.moveaxis(k, 2, 0),
                          jnp.moveaxis(v, 2, 0)))            # (KV, B, S, G, hd)
    o = jnp.moveaxis(o, 0, 2).reshape(b, s, H, hd)
    return num.mm("bshk,hkd->bsd", o, p["wo"])


def mlp(p, x, num):
    h = num.mm("bsd,df->bsf", x, p["wi"]) * silu(
        num.mm("bsd,df->bsf", x, p["wg"]))
    return num.mm("bsf,fd->bsd", h, p["wo"])


def loss(params, tokens, labels, conf, num):
    eps, r = conf["rms_norm_eps"], conf["residual_multiplier"]
    table = params["embed"]["table"]
    h = table[tokens] * conf["embedding_multiplier"]

    def layer(h, p):
        h = h + r * attention(p["attn"], rms_norm(h, p["ln1"]["scale"], eps),
                              conf, num)
        return h + r * mlp(p["mlp"], rms_norm(h, p["ln2"]["scale"], eps), num)

    h = layer_stack(layer, h, params["blocks"]["sb"]["slot0"], LAYER_GROUP)
    h = rms_norm(h, params["final_norm"]["scale"], eps)
    return token_loss(h / conf["logits_scaling"], table, labels, num,
                      conf["z_loss_coef"])
