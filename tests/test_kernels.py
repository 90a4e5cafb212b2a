"""Pallas kernel validation: shape/dtype sweeps vs ref.py oracles
(interpret=True executes the kernel body on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref


@pytest.mark.parametrize("S,causal,window", [
    (64, True, 0), (96, True, 0), (64, True, 16), (128, False, 0),
    (80, True, 24),
])
@pytest.mark.parametrize("dtype", [np.float32, jnp.bfloat16])
def test_flash_attention_sweep(S, causal, window, dtype):
    rng = np.random.RandomState(0)
    B, KV, G, hd = 2, 2, 2, 32
    q = rng.randn(B, S, KV, G, hd).astype(np.float32)
    k = rng.randn(B, S, KV, hd).astype(np.float32)
    v = rng.randn(B, S, KV, hd).astype(np.float32)
    qj, kj, vj = (jnp.asarray(x, dtype) for x in (q, k, v))
    o = ops.flash_attention(qj, kj, vj, causal=causal, window=window,
                            interpret=True, block_q=32, block_k=32)
    qf = np.moveaxis(q, 1, 3).reshape(B * KV * G, S, hd)
    kf = np.moveaxis(k, 1, 2).reshape(B * KV, S, hd)
    vf = np.moveaxis(v, 1, 2).reshape(B * KV, S, hd)
    oref = ref.flash_attention_oracle(jnp.asarray(qf), jnp.asarray(kf),
                                      jnp.asarray(vf), causal=causal,
                                      window=window)
    oref = np.moveaxis(np.asarray(oref, np.float32).reshape(B, KV, G, S, hd),
                       3, 1)
    atol = 2e-5 if dtype == np.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(o, np.float32), oref, atol=atol)


@pytest.mark.parametrize("mqa_kv", [1, 2, 4])
def test_flash_attention_gqa_ratios(mqa_kv):
    rng = np.random.RandomState(1)
    B, S, H, hd = 2, 64, 4, 16
    G = H // mqa_kv
    q = rng.randn(B, S, mqa_kv, G, hd).astype(np.float32)
    k = rng.randn(B, S, mqa_kv, hd).astype(np.float32)
    v = rng.randn(B, S, mqa_kv, hd).astype(np.float32)
    o = ops.flash_attention(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                            causal=True, interpret=True, block_q=32,
                            block_k=32)
    qf = np.moveaxis(q, 1, 3).reshape(B * mqa_kv * G, S, hd)
    kf = np.moveaxis(k, 1, 2).reshape(B * mqa_kv, S, hd)
    vf = np.moveaxis(v, 1, 2).reshape(B * mqa_kv, S, hd)
    oref = ref.flash_attention_oracle(jnp.asarray(qf), jnp.asarray(kf),
                                      jnp.asarray(vf), causal=True)
    oref = np.moveaxis(np.asarray(oref).reshape(B, mqa_kv, G, S, hd), 3, 1)
    np.testing.assert_allclose(np.asarray(o), oref, atol=2e-5)


@pytest.mark.parametrize("S,C,bt,bc", [(100, 48, 32, 16), (64, 64, 64, 64),
                                       (33, 7, 8, 8)])
def test_rglru_scan_sweep(S, C, bt, bc):
    rng = np.random.RandomState(2)
    B = 2
    a = 0.4 + 0.5 * jax.nn.sigmoid(
        jnp.asarray(rng.randn(B, S, C), jnp.float32))
    b = jnp.asarray(rng.randn(B, S, C), jnp.float32) * 0.1
    h = ops.rglru_scan(a, b, interpret=True, block_t=bt, block_c=bc)
    href = ref.rglru_scan_oracle(a, b)
    np.testing.assert_allclose(np.asarray(h), np.asarray(href), atol=1e-5)


def test_rglru_matches_associative_scan_path():
    from repro.models.rglru import rglru_scan_ref
    rng = np.random.RandomState(3)
    a = jax.nn.sigmoid(jnp.asarray(rng.randn(2, 50, 16), jnp.float32))
    b = jnp.asarray(rng.randn(2, 50, 16), jnp.float32)
    np.testing.assert_allclose(np.asarray(rglru_scan_ref(a, b)),
                               np.asarray(ref.rglru_scan_oracle(a, b)),
                               atol=1e-5)


@pytest.mark.parametrize("s,chunk", [(80, 32), (64, 64), (96, 16)])
def test_ssd_sweep(s, chunk):
    rng = np.random.RandomState(4)
    b, h, p, n = 2, 3, 16, 8
    x = jnp.asarray(rng.randn(b, s, h, p), jnp.float32)
    dt = jax.nn.softplus(jnp.asarray(rng.randn(b, s, h), jnp.float32))
    A = -jnp.exp(jnp.asarray(rng.randn(h), jnp.float32) * 0.3)
    B_ = jnp.asarray(rng.randn(b, s, n), jnp.float32) * 0.5
    C_ = jnp.asarray(rng.randn(b, s, n), jnp.float32) * 0.5
    y, sf = ops.ssd(x, dt, A, B_, C_, chunk=chunk, interpret=True)
    yr, sfr = ref.ssd_oracle(x, dt, A, B_, C_)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr), atol=2e-3)
    np.testing.assert_allclose(np.asarray(sf), np.asarray(sfr), atol=2e-3)


def test_ssd_chunked_model_path_matches_oracle():
    from repro.models.ssm import ssd_chunked
    rng = np.random.RandomState(5)
    b, s, h, p, n = 1, 48, 2, 8, 4
    x = jnp.asarray(rng.randn(b, s, h, p), jnp.float32)
    dt = jax.nn.softplus(jnp.asarray(rng.randn(b, s, h), jnp.float32))
    A = -jnp.exp(jnp.asarray(rng.randn(h), jnp.float32) * 0.3)
    B_ = jnp.asarray(rng.randn(b, s, n), jnp.float32) * 0.5
    C_ = jnp.asarray(rng.randn(b, s, n), jnp.float32) * 0.5
    y, sf = ssd_chunked(x, dt, A, B_, C_, 16)
    yr, sfr = ref.ssd_oracle(x, dt, A, B_, C_)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr), atol=2e-3)
    np.testing.assert_allclose(np.asarray(sf), np.asarray(sfr), atol=2e-3)


@pytest.mark.parametrize("window", [0, 24])
def test_flash_ref_gradient_matches_oracle(window):
    """The chunked flash takes no gradient through its running max (the
    output does not depend on it); its gradient is still the oracle's."""
    from repro.models.attention import flash_attention_ref
    rng = np.random.RandomState(7)
    B, S, KV, G, hd = 1, 64, 2, 2, 8
    q = jnp.asarray(rng.randn(B, S, KV, G, hd), jnp.float32)
    k = jnp.asarray(rng.randn(B, S, KV, hd), jnp.float32)
    v = jnp.asarray(rng.randn(B, S, KV, hd), jnp.float32)
    do = jnp.asarray(rng.randn(B, S, KV, G, hd), jnp.float32)

    def flash(q, k, v):
        return jnp.vdot(do, flash_attention_ref(
            q, k, v, scale=0.3, causal=True, window=window, q_block=16,
            kv_block=32))

    def oracle(q, k, v):
        qf = jnp.moveaxis(q, 1, 3).reshape(B * KV * G, S, hd)
        kf = jnp.moveaxis(k, 1, 2).reshape(B * KV, S, hd)
        vf = jnp.moveaxis(v, 1, 2).reshape(B * KV, S, hd)
        o = ref.flash_attention_oracle(qf, kf, vf, scale=0.3, causal=True,
                                       window=window)
        return jnp.vdot(do, jnp.moveaxis(o.reshape(B, KV, G, S, hd), 3, 1))

    got = jax.grad(flash, argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(oracle, argnums=(0, 1, 2))(q, k, v)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=1e-5)


def test_flash_ref_matches_oracle_property():
    """Property-style sweep of the jnp chunked-flash used in the XLA path."""
    from repro.models.attention import flash_attention_ref
    rng = np.random.RandomState(6)
    for trial in range(5):
        S = int(rng.choice([32, 48, 64, 96]))
        qb = int(rng.choice([16, 32]))
        kb = int(rng.choice([16, 32]))
        w = int(rng.choice([0, 8, 24]))
        B, KV, G, hd = 1, 2, 2, 8
        q = jnp.asarray(rng.randn(B, S, KV, G, hd), jnp.float32)
        k = jnp.asarray(rng.randn(B, S, KV, hd), jnp.float32)
        v = jnp.asarray(rng.randn(B, S, KV, hd), jnp.float32)
        o = flash_attention_ref(q, k, v, scale=0.3, causal=True, window=w,
                                q_block=qb, kv_block=kb)
        qf = jnp.moveaxis(q, 1, 3).reshape(B * KV * G, S, hd)
        kf = jnp.moveaxis(k, 1, 2).reshape(B * KV, S, hd)
        vf = jnp.moveaxis(v, 1, 2).reshape(B * KV, S, hd)
        oref = ref.flash_attention_oracle(qf, kf, vf, scale=0.3, causal=True,
                                          window=w)
        oref = jnp.moveaxis(oref.reshape(B, KV, G, S, hd), 3, 1)
        np.testing.assert_allclose(np.asarray(o), np.asarray(oref), atol=1e-5)
