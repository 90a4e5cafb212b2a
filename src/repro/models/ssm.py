"""Mamba2 block: in_proj -> causal conv -> SSD (state-space duality) -> gated out.

The SSD scan is the chunked algorithm of arXiv:2405.21060 SS6 — quadratic
attention-like compute within chunks, linear recurrence between chunk states.
A Pallas TPU kernel implements the same contraction (kernels/ssd.py); this
module is the jnp implementation used for lowering and as the oracle.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.layers import ParamSpec
from repro.obs import scopes


def ssd_specs(cfg):
    """in_proj is split (x/z/B/C/dt) so each output dim keeps a cleanly
    shardable logical axis (the fused 2*di+2n+nh dim is not divisible by a
    16-way model axis)."""
    d, di, n, nh = cfg.d_model, cfg.d_inner, cfg.ssm_state, cfg.ssm_heads
    conv_dim = di + 2 * n
    return {
        "in_x": ParamSpec((d, di), ("embed", "inner")),
        "in_z": ParamSpec((d, di), ("embed", "inner")),
        "in_B": ParamSpec((d, n), ("embed", None)),
        "in_C": ParamSpec((d, n), ("embed", None)),
        "in_dt": ParamSpec((d, nh), ("embed", "heads")),
        "conv_w": ParamSpec((cfg.conv_width, conv_dim), (None, "inner")),
        "conv_b": ParamSpec((conv_dim,), ("inner",), init="zeros"),
        "dt_bias": ParamSpec((nh,), (None,), init="zeros", dtype=jnp.float32),
        "A_log": ParamSpec((nh,), (None,), init="ones", dtype=jnp.float32),
        "D": ParamSpec((nh,), (None,), init="ones", dtype=jnp.float32),
        "out_proj": ParamSpec((di, d), ("inner", "embed")),
    }


def _causal_conv(x, w, b):
    """Depthwise causal conv via shifted adds.  x (B,S,C); w (W,C)."""
    W = w.shape[0]
    out = x * w[-1]
    for i in range(1, W):
        shifted = jnp.pad(x, ((0, 0), (i, 0), (0, 0)))[:, :-i or None][:, :x.shape[1]]
        out = out + shifted * w[W - 1 - i]
    return out + b


def ssd_chunked(x, dt, A, B, C, chunk):
    """SSD scan.  x (b,s,h,p); dt (b,s,h); A (h,); B,C (b,s,n) (one group).

    Returns y (b,s,h,p).  Everything in f32.
    """
    b, s, h, p = x.shape
    n = B.shape[-1]
    pad = (-s) % chunk
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        B = jnp.pad(B, ((0, 0), (0, pad), (0, 0)))
        C = jnp.pad(C, ((0, 0), (0, pad), (0, 0)))
    S = s + pad
    nc = S // chunk
    xc = x.reshape(b, nc, chunk, h, p)
    dtc = dt.reshape(b, nc, chunk, h)
    Bc = B.reshape(b, nc, chunk, n)
    Cc = C.reshape(b, nc, chunk, n)

    # named_scope: VMEM-resident in the Pallas SSD kernel (kernels/ssd.py)
    with jax.named_scope("ssd_vmem"):
        a = dtc * A[None, None, None, :]                  # (b,nc,Q,h) log-decay
        cum = jnp.cumsum(a, axis=2)                       # inclusive
        # intra-chunk: scores[i,j] = (C_i . B_j) * exp(cum_i - cum_j) * dt_j
        seg = cum[:, :, :, None, :] - cum[:, :, None, :, :]   # (b,nc,Q,Q,h)
        causal = jnp.tril(jnp.ones((chunk, chunk), bool))[None, None, :, :, None]
        # mask before the exp: above the diagonal seg > 0 overflows, and the
        # where's zero cotangent times exp(seg) = inf would make the
        # gradient NaN
        L = jnp.exp(jnp.where(causal, seg, -jnp.inf))
        cb = jnp.einsum("bcin,bcjn->bcij", Cc, Bc)        # (b,nc,Q,Q)
        scores = cb[..., None] * L * dtc[:, :, None, :, :]    # (b,nc,Q,Q,h)
        y_intra = jnp.einsum("bcijh,bcjhp->bcihp", scores, xc)

        # chunk states: S_c = sum_j exp(cum_last - cum_j) dt_j B_j (x) x_j
        decay_end = jnp.exp(cum[:, :, -1:, :] - cum)      # (b,nc,Q,h)
        wB = Bc[:, :, :, None, :] * (dtc * decay_end)[..., None]  # (b,nc,Q,h,n)
        S_c = jnp.einsum("bcjhn,bcjhp->bchnp", wB, xc)    # (b,nc,h,n,p)

    # inter-chunk recurrence: S_{c} passed with decay exp(sum a over chunk)
    chunk_decay = jnp.exp(cum[:, :, -1, :])               # (b,nc,h)

    def scan_fn(S_prev, inp):
        dec, S_new = inp                                  # (b,h), (b,h,n,p)
        S_out = S_prev * dec[:, :, None, None] + S_new
        return S_out, S_prev

    S0 = jnp.zeros((b, h, n, p), jnp.float32)
    S_final, S_prevs = jax.lax.scan(
        scan_fn, S0, (jnp.moveaxis(chunk_decay, 1, 0), jnp.moveaxis(S_c, 1, 0)))
    S_prevs = jnp.moveaxis(S_prevs, 0, 1)                 # (b,nc,h,n,p) state entering chunk

    # inter-chunk contribution: y_i += C_i . (exp(cum_i) * S_prev)
    y_inter = jnp.einsum("bcin,bchnp->bcihp", Cc, S_prevs) \
        * jnp.exp(cum)[..., None]
    y = (y_intra + y_inter).reshape(b, S, h, p)[:, :s]
    return y, S_final


def ssd_block_apply(p, x, cfg, ctx, collect_cache=False):
    """Full mamba2 mixer.  x (B,S,D) -> (out (B,S,D), cache|None)."""
    B_, S_, D_ = x.shape
    di, n, nh, hp = cfg.d_inner, cfg.ssm_state, cfg.ssm_heads, cfg.ssm_head_dim
    z = jnp.einsum("bsd,de->bse", x, p["in_z"])
    xBC_raw = jnp.concatenate([
        jnp.einsum("bsd,de->bse", x, p["in_x"]),
        jnp.einsum("bsd,dn->bsn", x, p["in_B"]),
        jnp.einsum("bsd,dn->bsn", x, p["in_C"])], axis=-1)
    dt = jnp.einsum("bsd,dh->bsh", x, p["in_dt"])
    xBC = jax.nn.silu(_causal_conv(xBC_raw, p["conv_w"], p["conv_b"]))
    xs, Bs, Cs = jnp.split(xBC, [di, di + n], axis=-1)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"])
    A = -jnp.exp(p["A_log"])
    xh = xs.reshape(B_, S_, nh, hp).astype(jnp.float32)
    with jax.named_scope(scopes.SEQ_MIX):
        if ctx.attn_impl in ("pallas", "interpret"):
            from repro.kernels import ops as kops
            y, S_final = kops.ssd(xh, dt, A, Bs.astype(jnp.float32),
                                  Cs.astype(jnp.float32), chunk=cfg.ssm_chunk,
                                  interpret=(ctx.attn_impl == "interpret"))
        else:
            y, S_final = ssd_chunked(xh, dt, A, Bs.astype(jnp.float32),
                                     Cs.astype(jnp.float32), cfg.ssm_chunk)
    cache = None
    if collect_cache:
        cw = cfg.conv_width
        conv_buf = xBC_raw[:, -(cw - 1):]
        if S_ < cw - 1:
            conv_buf = jnp.pad(xBC_raw, ((0, 0), (cw - 1 - S_, 0), (0, 0)))
        cache = {"state": S_final, "conv": conv_buf.astype(jnp.bfloat16)}
    y = y + p["D"][None, None, :, None] * xh
    y = y.reshape(B_, S_, di).astype(x.dtype)
    y = y * jax.nn.silu(z)
    y = ctx.shard(y, "batch", "seq", "inner")
    return jnp.einsum("bse,ed->bsd", y, p["out_proj"]), cache


# ---------------------------------------------------------------------------
# decode (single-token recurrence)
# ---------------------------------------------------------------------------

def init_ssd_cache(cfg, batch):
    di, n = cfg.d_inner, cfg.ssm_state
    conv_dim = di + 2 * n
    return {
        "state": jnp.zeros((batch, cfg.ssm_heads, n, cfg.ssm_head_dim), jnp.float32),
        "conv": jnp.zeros((batch, cfg.conv_width - 1, conv_dim), jnp.bfloat16),
    }


def ssd_block_decode(p, x, cache, cfg, ctx):
    """x (B,1,D); single-step SSM recurrence."""
    B_ = x.shape[0]
    di, n, nh, hp = cfg.d_inner, cfg.ssm_state, cfg.ssm_heads, cfg.ssm_head_dim
    x1 = x[:, 0]
    z = jnp.einsum("bd,de->be", x1, p["in_z"])
    xBC = jnp.concatenate([
        jnp.einsum("bd,de->be", x1, p["in_x"]),
        jnp.einsum("bd,dn->bn", x1, p["in_B"]),
        jnp.einsum("bd,dn->bn", x1, p["in_C"])], axis=-1)
    dt = jnp.einsum("bd,dh->bh", x1, p["in_dt"])
    # conv over buffer + current
    hist = jnp.concatenate([cache["conv"].astype(xBC.dtype), xBC[:, None, :]], axis=1)
    conv_out = jnp.einsum("bwc,wc->bc", hist, p["conv_w"]) + p["conv_b"]
    xBC = jax.nn.silu(conv_out)
    new_conv = hist[:, 1:].astype(cache["conv"].dtype)
    xs, Bs, Cs = jnp.split(xBC, [di, di + n], axis=-1)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"])       # (B,nh)
    A = -jnp.exp(p["A_log"])
    a = jnp.exp(dt * A)                                               # (B,nh)
    xh = xs.reshape(B_, nh, hp).astype(jnp.float32)
    dBx = jnp.einsum("bn,bhp->bhnp", Bs.astype(jnp.float32), xh) \
        * dt[:, :, None, None]
    state = cache["state"] * a[:, :, None, None] + dBx
    y = jnp.einsum("bn,bhnp->bhp", Cs.astype(jnp.float32), state)
    y = y + p["D"][None, :, None] * xh
    y = y.reshape(B_, di).astype(x.dtype) * jax.nn.silu(z)
    out = jnp.einsum("be,ed->bd", y, p["out_proj"])[:, None, :]
    return out, {"state": state, "conv": new_conv}
