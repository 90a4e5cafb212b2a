"""Attention: global (causal), local (sliding window), cross; train + decode.

Implementations (ParallelConfig.attn_impl):
  * "xla"       -- chunked-scan flash attention in pure jnp: O(S * block)
                   memory, lowers on any backend, used for the dry-run.
  * "pallas"    -- TPU Pallas kernel (kernels/flash_attention.py).
  * "interpret" -- same kernel, interpret=True (CPU tests).
  * "naive"     -- materialized scores; tiny shapes only (oracle).

Layouts: q (B, S, H, hd); k/v (B, S, KV, hd).  GQA is expressed by grouping
q as (B, S, KV, G, hd) inside the score einsums so that k/v broadcast over G
without materializing repeated heads.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

from repro.models.layers import ParamSpec, rms_norm, rms_norm_specs, rope
from repro.obs import scopes

NEG_INF = -2.0e38


# ---------------------------------------------------------------------------
# specs
# ---------------------------------------------------------------------------

def attention_specs(cfg, cross: bool = False):
    d, h, kv, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    s = {
        "wq": ParamSpec((d, h, hd), ("embed", "heads", "head_dim")),
        "wk": ParamSpec((d, kv, hd), ("embed", "kv_heads", "head_dim")),
        "wv": ParamSpec((d, kv, hd), ("embed", "kv_heads", "head_dim")),
        "wo": ParamSpec((h, hd, d), ("heads", "head_dim", "embed")),
    }
    if cfg.qk_norm:
        s["qnorm"] = rms_norm_specs(hd)
        s["knorm"] = rms_norm_specs(hd)
    if cross:
        # gated cross-attention (llama-3.2-vision style tanh gate)
        s["gate"] = ParamSpec((), (), init="zeros", dtype=jnp.float32)
    return s


def _group(q, kv_heads):
    """(B, S, H, hd) -> (B, S, KV, G, hd)."""
    b, s, h, hd = q.shape
    return q.reshape(b, s, kv_heads, h // kv_heads, hd)


def _ungroup(o):
    b, s, kvh, g, hd = o.shape
    return o.reshape(b, s, kvh * g, hd)


def _project_qkv(p, x, memory, cfg, ctx, rope_theta, positions, kind):
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    src = x if memory is None else memory
    k = jnp.einsum("bsd,dhk->bshk", src, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", src, p["wv"])
    if cfg.qk_norm:
        q = rms_norm(q, p["qnorm"]["scale"], cfg.norm_eps)
        k = rms_norm(k, p["knorm"]["scale"], cfg.norm_eps)
    if kind != "cross" and rope_theta:
        q = rope(q, positions, rope_theta)
        k = rope(k, positions, rope_theta)
    # NOTE: seq stays unsharded here (None, not "seq") — sequence parallelism
    # applies to the residual stream only; re-sharding blocked flash scans
    # over a seq-sharded operand makes GSPMD re-gather every scan step.
    q = ctx.shard(q, "batch", None, "heads", None)
    k = ctx.shard(k, "batch", None, "kv_heads", None)
    v = ctx.shard(v, "batch", None, "kv_heads", None)
    return q, k, v


def _out_proj(p, o, ctx, gated=False):
    out = jnp.einsum("bshk,hkd->bsd", o, p["wo"])
    if gated:
        out = out * jnp.tanh(p["gate"]).astype(out.dtype)
    return out


# ---------------------------------------------------------------------------
# score-level attention primitives (jnp)
# ---------------------------------------------------------------------------

def naive_attention(q, k, v, mask, scale):
    """q (B,Sq,KV,G,hd), k/v (B,Sk,KV,hd), mask broadcastable to (B,KV,G,Sq,Sk)."""
    s = jnp.einsum("bqkgh,bskh->bkgqs", q, k).astype(jnp.float32) * scale
    s = jnp.where(mask, s, NEG_INF)
    w = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgqs,bskh->bqkgh", w.astype(v.dtype), v)
    return o


def flash_attention_ref(q, k, v, *, scale, causal=True, window=0,
                        q_block=1024, kv_block=1024, q_offset=0):
    """Chunked-scan flash attention (pure jnp, any backend).

    q (B,Sq,KV,G,hd); k/v (B,Sk,KV,hd).  Sequential scan over q blocks; inner
    scan over kv blocks with running (m, l, acc).  q_offset: absolute position
    of q[0] relative to k[0] (for cached decode-prefill continuation).
    """
    B, Sq, KV, G, hd = q.shape
    Sk = k.shape[1]
    q_block = min(q_block, Sq)
    kv_block = min(kv_block, Sk)
    # pad to block multiples
    pq = (-Sq) % q_block
    pk = (-Sk) % kv_block
    if pq:
        q = jnp.pad(q, ((0, 0), (0, pq), (0, 0), (0, 0), (0, 0)))
    if pk:
        k = jnp.pad(k, ((0, 0), (0, pk), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pk), (0, 0), (0, 0)))
    nq, nk = (Sq + pq) // q_block, (Sk + pk) // kv_block

    qs = jnp.moveaxis(q.reshape(B, nq, q_block, KV, G, hd), 1, 0)
    ks = jnp.moveaxis(k.reshape(B, nk, kv_block, KV, hd), 1, 0)
    vs = jnp.moveaxis(v.reshape(B, nk, kv_block, KV, hd), 1, 0)
    qpos = jnp.arange(nq * q_block).reshape(nq, q_block) + q_offset
    kpos = jnp.arange(nk * kv_block).reshape(nk, kv_block)

    def q_body(_, xq):
        q_i, pq_i = xq

        def kv_body(carry, xk):
            # named_scope marks the VMEM-resident region of the Pallas flash
            # kernel: the roofline's fused-kernel accounting drops HBM bytes
            # for ops inside it (kernels/flash_attention.py is the TPU impl)
            with jax.named_scope("flash_vmem"):
                m, l, acc = carry
                k_j, v_j, pk_j = xk
                s = jnp.einsum("bqkgh,bskh->bkgqs", q_i, k_j).astype(jnp.float32) * scale
                msk = pk_j[None, :] <= pq_i[:, None]            # causal
                if window:
                    msk &= (pq_i[:, None] - pk_j[None, :]) < window
                msk &= pk_j[None, :] < Sk                        # kv padding
                s = jnp.where(msk[None, None, None], s, NEG_INF)
                # the running max only keeps exp() in range; the output
                # does not depend on it, so no gradient goes through it.
                # (Through the max's own gradient, which divides by the
                # count of scores equal to the max, dq and dk of this scan
                # came out all NaN on a TPU v5e at granite-3-8b's widths.)
                m_new = jax.lax.stop_gradient(
                    jnp.maximum(m, s.max(axis=-1)))
                alpha = jnp.exp(m - m_new)
                pr = jnp.exp(s - m_new[..., None])
                l_new = l * alpha + pr.sum(axis=-1)
                acc_new = (acc * alpha[..., None]
                           + jnp.einsum("bkgqs,bskh->bkgqh", pr.astype(v_j.dtype), v_j)
                           .astype(jnp.float32))
            return (m_new, l_new, acc_new), None

        init = (jnp.full((B, KV, G, q_block), NEG_INF, jnp.float32),
                jnp.zeros((B, KV, G, q_block), jnp.float32),
                jnp.zeros((B, KV, G, q_block, hd), jnp.float32))
        (m, l, acc), _ = jax.lax.scan(kv_body, init, (ks, vs, kpos))
        with jax.named_scope("flash_vmem"):      # kernel epilogue (VMEM)
            o = acc / jnp.maximum(l, 1e-37)[..., None]
            o = o.astype(q.dtype)
        return None, o

    _, outs = jax.lax.scan(q_body, None, (qs, qpos))         # (nq,B,KV,G,qb,hd)
    o = jnp.moveaxis(outs, 0, 3)                             # (B,KV,G,nq,qb,hd)
    o = o.reshape(B, KV, G, nq * q_block, hd)
    o = jnp.moveaxis(o, 3, 1)[:, :Sq]                        # (B,Sq,KV,G,hd)
    return o


def local_block_attention(q, k, v, *, scale, window):
    """Banded local attention: block size == window, each q block attends to
    its own + previous block.  Exact for sliding window `window`.
    q (B,S,KV,G,hd); k/v (B,S,KV,hd)."""
    B, S, KV, G, hd = q.shape
    w = window
    pad = (-S) % w
    if pad:
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0), (0, 0)))
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    Sp = S + pad
    nb = Sp // w
    qb = q.reshape(B, nb, w, KV, G, hd)
    kb = k.reshape(B, nb, w, KV, hd)
    vb = v.reshape(B, nb, w, KV, hd)
    # previous block (zeros for block 0)
    shift = lambda x: jnp.pad(x, ((0, 0), (1, 0)) + ((0, 0),) * (x.ndim - 2))[:, :-1]
    k2 = jnp.concatenate([shift(kb), kb], axis=2)            # (B,nb,2w,KV,hd)
    v2 = jnp.concatenate([shift(vb), vb], axis=2)
    with jax.named_scope("flash_vmem"):
        s = jnp.einsum("bnqkgh,bnskh->bnkgqs", qb, k2).astype(jnp.float32) * scale
        qpos = jnp.arange(nb * w).reshape(nb, w)
        # absolute key positions per block row: previous block then own block
        kpos = (jnp.arange(nb)[:, None] - 1) * w + jnp.arange(2 * w)[None, :]
        msk = (kpos[:, None, :] <= qpos[:, :, None]) \
            & (qpos[:, :, None] - kpos[:, None, :] < w) \
            & (kpos[:, None, :] >= 0) & (kpos[:, None, :] < S)
        s = jnp.where(msk[None, :, None, None], s, NEG_INF)
        pr = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bnkgqs,bnskh->bnqkgh", pr.astype(v2.dtype), v2)
    o = o.reshape(B, Sp, KV, G, hd)[:, :S]
    return o


# ---------------------------------------------------------------------------
# full-sequence layer entry (train / prefill)
# ---------------------------------------------------------------------------

def attention_apply(p, x, cfg, ctx, kind, memory=None, positions=None):
    """x (B,S,D).  kind in {global, local, cross, enc}.

    Returns (out (B,S,D), (k, v)) — roped keys/values so callers can build a
    decode cache from a prefill pass.
    """
    B, S, D = x.shape
    if positions is None:
        positions = jnp.arange(S)[None, :]
    theta = cfg.rope_theta
    if kind == "global" and cfg.rope_theta_global:
        theta = cfg.rope_theta_global
    q, k, v = _project_qkv(p, x, memory, cfg, ctx, theta, positions, kind)
    scale = 1.0 / math.sqrt(cfg.head_dim)
    qg = _group(q, cfg.num_kv_heads)

    impl = ctx.attn_impl
    causal = kind in ("global", "local")
    window = cfg.local_window if kind == "local" else 0

    with jax.named_scope(scopes.SEQ_MIX):
        if impl in ("pallas", "interpret"):
            from repro.kernels import ops as kops
            o = kops.flash_attention(qg, k, v, causal=causal, window=window,
                                     scale=scale,
                                     interpret=(impl == "interpret"))
        elif impl == "naive" or not causal:
            # cross / encoder attention: full (no mask or memory-length mask)
            Sk = k.shape[1]
            if causal:
                msk = jnp.tril(jnp.ones((S, Sk), bool))[None, None, None]
            else:
                msk = jnp.ones((1, 1, 1, 1, 1), bool)
            if impl == "naive" and causal and window:
                qp = jnp.arange(S)[:, None]
                kp = jnp.arange(Sk)[None, :]
                msk = ((kp <= qp) & (qp - kp < window))[None, None, None]
            o = naive_attention(qg, k, v, msk, scale)
        elif kind == "local":
            o = local_block_attention(qg, k, v, scale=scale, window=window)
        else:
            o = flash_attention_ref(qg, k, v, scale=scale, causal=True,
                                    q_block=ctx.q_block,
                                    kv_block=ctx.kv_block)
    o = _ungroup(o)
    o = ctx.shard(o, "batch", None, "heads", None)
    return _out_proj(p, o, ctx, gated=(kind == "cross")), (k, v)


def pack_prefill_cache(k, v, kind, cfg, cache_len):
    """Arrange full-sequence roped (k, v) (B,S,KV,hd) into the decode cache
    layout of init_attn_cache (ring order for local windows)."""
    B, S = k.shape[:2]
    if kind == "local":
        W = min(cfg.local_window, cache_len)
        if S >= W:
            k_t, v_t = k[:, S - W:], v[:, S - W:]
            # position p lands at slot p % W; first kept position is S-W
            shift = S % W
            k_c = jnp.roll(k_t, shift, axis=1)
            v_c = jnp.roll(v_t, shift, axis=1)
        else:
            pad = ((0, 0), (0, W - S), (0, 0), (0, 0))
            k_c, v_c = jnp.pad(k, pad), jnp.pad(v, pad)
        return {"k": k_c.astype(jnp.bfloat16), "v": v_c.astype(jnp.bfloat16)}
    L = cache_len if kind != "cross" else k.shape[1]
    if S < L:
        pad = ((0, 0), (0, L - S), (0, 0), (0, 0))
        k, v = jnp.pad(k, pad), jnp.pad(v, pad)
    else:
        k, v = k[:, :L], v[:, :L]
    return {"k": k.astype(jnp.bfloat16), "v": v.astype(jnp.bfloat16)}


# ---------------------------------------------------------------------------
# decode (single token, cached)
# ---------------------------------------------------------------------------

def init_attn_cache(cfg, kind, batch, cache_len, ctx=None):
    kv, hd = cfg.num_kv_heads, cfg.head_dim
    if kind == "local":
        L = min(cfg.local_window, cache_len)
    elif kind == "cross":
        L = cfg.context_tokens or cfg.encoder_len
    else:
        L = cache_len
    z = lambda: jnp.zeros((batch, L, kv, hd), jnp.bfloat16)
    return {"k": z(), "v": z()}


def attention_decode(p, x, cache, pos, cfg, ctx, kind, memory=None):
    """x (B,1,D); cache {"k","v"} (B,L,KV,hd); pos scalar int32 (tokens so far).

    Returns (out (B,1,D), new_cache).  For "cross", cache holds the static
    memory KV (written at prefill; here just read).
    """
    B = x.shape[0]
    theta = cfg.rope_theta
    if kind == "global" and cfg.rope_theta_global:
        theta = cfg.rope_theta_global
    positions = jnp.full((B, 1), pos, jnp.int32)
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    if cfg.qk_norm:
        q = rms_norm(q, p["qnorm"]["scale"], cfg.norm_eps)
    if kind != "cross" and theta:
        q = rope(q, positions, theta)
    scale = 1.0 / math.sqrt(cfg.head_dim)
    qg = _group(q, cfg.num_kv_heads)                    # (B,1,KV,G,hd)

    if kind == "cross":
        k, v = cache["k"], cache["v"]
        msk = jnp.ones((1, 1, 1, 1, 1), bool)
        o = naive_attention(qg, k, v, msk, scale)
        o = _ungroup(o)
        return _out_proj(p, o, ctx, gated=True), cache

    k_new = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
    v_new = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
    if cfg.qk_norm:
        k_new = rms_norm(k_new, p["knorm"]["scale"], cfg.norm_eps)
    if theta:
        k_new = rope(k_new, positions, theta)

    L = cache["k"].shape[1]
    slot = pos % L if kind == "local" else pos
    k_cache = jax.lax.dynamic_update_slice(cache["k"], k_new.astype(cache["k"].dtype),
                                           (0, slot, 0, 0))
    v_cache = jax.lax.dynamic_update_slice(cache["v"], v_new.astype(cache["v"].dtype),
                                           (0, slot, 0, 0))
    k_cache = ctx.shard(k_cache, "batch", "cache", "kv_heads", None)
    v_cache = ctx.shard(v_cache, "batch", "cache", "kv_heads", None)

    slots = jnp.arange(L)
    if kind == "local":
        # slot s holds absolute position pos - ((pos - s) mod L); valid if >= 0
        p_slot = pos - ((pos - slots) % L)
        valid = (p_slot >= 0) & (p_slot <= pos) & (pos - p_slot < cfg.local_window)
    else:
        valid = slots <= pos
    s = jnp.einsum("bqkgh,bskh->bkgqs", qg, k_cache).astype(jnp.float32) * scale
    s = jnp.where(valid[None, None, None, None, :], s, NEG_INF)
    # flash-decoding across chips: keep the score vector sharded along the
    # cache dim; GSPMD turns the softmax stats into small all-reduces instead
    # of re-gathering the (huge) cache shards (long_500k)
    s = ctx.shard(s, "batch", "kv_heads", None, None, "cache")
    w = jax.nn.softmax(s, axis=-1)
    w = ctx.shard(w, "batch", "kv_heads", None, None, "cache")
    o = jnp.einsum("bkgqs,bskh->bqkgh", w.astype(v_cache.dtype), v_cache)
    o = _ungroup(o)
    out = _out_proj(p, o, ctx)
    return out, {"k": k_cache, "v": v_cache}
