"""Plain float32 Mamba2 language model, from the configuration file alone.

The mixer follows arXiv:2405.21060: in-projections of x, z, B, C and dt; a
depthwise causal convolution with SiLU over (x, B, C); the selective
state-space recurrence h_t = exp(dt_t A) h_{t-1} + dt_t B_t x_t^T,
y_t = C_t h_t + D x_t, computed in its quadratic (attention-like) form over
the whole sequence, not in chunks; y gated by SiLU(z); out-projection.

Departures from the published model, all stated in the configuration file:
no gated RMSNorm before the out-projection (``ssm_cfg.rmsnorm`` false), the
embedding scaled by ``embedding_multiplier``, and the loss with a z-loss
term. The parameters come in the layout of the benchmark's weights
(``weights.py``): layers stacked along a leading axis under blocks/sb/slot0.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench.reflib import layer_stack, rms_norm, silu, softplus, token_loss

HEAD_BLOCK = 16          # heads whose (S, S) decay matrices exist at once
LAYER_GROUP = 6          # layers whose inputs are kept while one is recomputed


def causal_conv(x, w, b):
    """x (B, S, C); w (W, C): out_t = b + sum_k w[k] x_{t - (W - 1 - k)}."""
    W, S = w.shape[0], x.shape[1]
    xp = jnp.pad(x, ((0, 0), (W - 1, 0), (0, 0)))
    return b + sum(w[k] * xp[:, k:k + S] for k in range(W))


def ssd(x, dt, A, Bm, Cm, num):
    """x (B, S, H, P); dt (B, S, H); A (H,); Bm, Cm (B, S, N) -> y (B, S, H, P).

    y_i = sum_{j <= i} (C_i . B_j) exp(sum_{j < k <= i} dt_k A) dt_j x_j."""
    b, s, H, P = x.shape
    cum = jnp.cumsum(dt * A, axis=1)                        # (B, S, H)
    causal = jnp.tril(jnp.ones((s, s), bool))
    hb = max(g for g in range(1, min(HEAD_BLOCK, H) + 1) if H % g == 0)
    nb = H // hb

    @jax.checkpoint
    def one(args):                                          # one sequence, hb heads
        cb, cum_h, dt_h, x_h = args                         # (S,S) (S,hb) (S,hb) (S,hb,P)
        seg = cum_h[:, None, :] - cum_h[None, :, :]         # (i, j, hb)
        decay = jnp.exp(jnp.where(causal[:, :, None], seg, -jnp.inf))
        w = cb[:, :, None] * decay * dt_h[None, :, :]
        return num.mm("ijh,jhp->ihp", w, x_h)

    cb = num.mm("bin,bjn->bij", Cm, Bm)                     # one group of B, C
    split = lambda a: jnp.moveaxis(a.reshape((b, s, nb, hb) + a.shape[3:]),
                                   2, 1).reshape((b * nb, s, hb) + a.shape[3:])
    cbs = jnp.repeat(cb, nb, axis=0)
    y = jax.lax.map(one, (cbs, split(cum), split(dt), split(x)))
    y = y.reshape(b, nb, s, hb, P)
    return jnp.moveaxis(y, 1, 2).reshape(b, s, H, P)


def mixer(p, x, conf, num):
    ssm = conf["ssm_cfg"]
    N, P = ssm["d_state"], ssm["headdim"]
    di = ssm["expand"] * conf["d_model"]
    H = di // P
    b, s, _ = x.shape
    z = num.mm("bsd,de->bse", x, p["in_z"])
    xbc = jnp.concatenate([num.mm("bsd,de->bse", x, p["in_x"]),
                           num.mm("bsd,dn->bsn", x, p["in_B"]),
                           num.mm("bsd,dn->bsn", x, p["in_C"])], axis=-1)
    dt = softplus(num.mm("bsd,dh->bsh", x, p["in_dt"]) + p["dt_bias"])
    xbc = silu(causal_conv(xbc, p["conv_w"], p["conv_b"]))
    xs, Bm, Cm = xbc[..., :di], xbc[..., di:di + N], xbc[..., di + N:]
    xh = xs.reshape(b, s, H, P)
    y = ssd(xh, dt, -jnp.exp(p["A_log"]), Bm, Cm, num) + p["D"][:, None] * xh
    y = y.reshape(b, s, di) * silu(z)
    return num.mm("bse,ed->bsd", y, p["out_proj"])


def loss(params, tokens, labels, conf, num):
    eps = conf["rms_norm_eps"]
    table = params["embed"]["table"]
    h = table[tokens] * conf["embedding_multiplier"]

    def layer(h, p):
        return h + mixer(p["mixer"], rms_norm(h, p["ln1"]["scale"], eps),
                         conf, num)

    h = layer_stack(layer, h, params["blocks"]["sb"]["slot0"], LAYER_GROUP)
    h = rms_norm(h, params["final_norm"]["scale"], eps)
    return token_loss(h, table, labels, num, conf["z_loss_coef"])
