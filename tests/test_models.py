"""Per-arch smoke tests: reduced configs, one forward/train step on CPU,
shape + finiteness assertions; prefill/decode consistency."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import ARCH_NAMES, get_config
from repro.models import Ctx, build_model


def _mk(name):
    cfg = get_config(name, smoke=True)
    m = build_model(cfg)
    params = m.init(jax.random.PRNGKey(0))
    return cfg, m, params


def _batch(cfg, m, B=2, S=48):
    tokens = jax.random.randint(jax.random.PRNGKey(1), (B, S + 1), 0,
                                cfg.vocab_size)
    mem = None
    ml = m.memory_len()
    if ml:
        mem = jax.random.normal(jax.random.PRNGKey(2), (B, ml, cfg.d_model),
                                jnp.bfloat16)
    return tokens, mem


@pytest.mark.parametrize("name", ARCH_NAMES)
def test_forward_shapes_and_finite(name):
    cfg, m, params = _mk(name)
    tokens, mem = _batch(cfg, m)
    ctx = Ctx()
    logits, aux = m.apply(params, tokens[:, :-1], ctx, memory=mem)
    assert logits.shape == (2, 48, cfg.vocab_size)
    assert np.isfinite(np.asarray(logits, np.float32)).all()


@pytest.mark.parametrize("name", ARCH_NAMES)
def test_one_train_step(name):
    from repro.configs.base import ParallelConfig
    from repro.train import OptConfig, init_train_state, make_train_step
    cfg, m, params = _mk(name)
    tokens, mem = _batch(cfg, m)
    batch = {"tokens": tokens[:, :-1], "labels": tokens[:, 1:]}
    if mem is not None:
        batch["memory"] = mem
    state = init_train_state(m, jax.random.PRNGKey(0), ParallelConfig())
    step = jax.jit(make_train_step(m, OptConfig(lr=1e-3, warmup_steps=1,
                                                total_steps=10),
                                   ParallelConfig()))
    state2, metrics = step(state, batch)
    loss = float(metrics["loss"])
    assert np.isfinite(loss) and 0 < loss < 20
    # params actually changed
    d0 = jax.tree_util.tree_leaves(state.params)[1]
    d1 = jax.tree_util.tree_leaves(state2.params)[1]
    assert not np.allclose(np.asarray(d0, np.float32),
                           np.asarray(d1, np.float32))


@pytest.mark.parametrize("name", ARCH_NAMES)
def test_prefill_decode_matches_forward(name):
    cfg, m, params = _mk(name)
    S, cache_len = 48, 64
    tokens, mem = _batch(cfg, m, S=S)
    ctx = Ctx()
    logits_full, _ = m.apply(params, tokens, ctx, memory=mem)
    last, cache = m.prefill(params, tokens[:, :S], ctx, cache_len, memory=mem)
    np.testing.assert_allclose(np.asarray(last),
                               np.asarray(logits_full[:, S - 1]),
                               atol=1e-3, rtol=1e-2)
    dl, cache = m.decode_step(params, tokens[:, S:S + 1], cache, ctx,
                              memory=mem)
    err = float(jnp.max(jnp.abs(dl - logits_full[:, S])))
    assert err < 0.15, f"{name} decode mismatch {err}"


@pytest.mark.parametrize("name", ["recurrentgemma-9b", "mamba2-780m",
                                  "qwen3-8b", "gemma3-4b"])
def test_kernel_impl_matches_xla(name):
    # (MoE archs excluded: capacity-based routing amplifies bf16 noise into
    # discrete expert-assignment flips, so logit comparison is ill-posed)
    cfg, m, params = _mk(name)
    tokens, mem = _batch(cfg, m)
    lx, _ = m.apply(params, tokens[:, :-1], Ctx(attn_impl="xla"), memory=mem)
    lk, _ = m.apply(params, tokens[:, :-1], Ctx(attn_impl="interpret"),
                    memory=mem)
    assert float(jnp.max(jnp.abs(lx - lk))) < 0.3


def test_kernel_impl_matches_xla_swa_dense():
    """Sliding-window flash kernel vs XLA banded attention on a dense model
    (mixtral layer pattern with the MoE router disabled)."""
    cfg = get_config("mixtral-8x7b", smoke=True).replace(
        name="swa-dense", num_experts=0, experts_per_token=0)
    m = build_model(cfg)
    params = m.init(jax.random.PRNGKey(0))
    tokens, _ = _batch(cfg, m)
    lx, _ = m.apply(params, tokens[:, :-1], Ctx(attn_impl="xla"))
    lk, _ = m.apply(params, tokens[:, :-1], Ctx(attn_impl="interpret"))
    assert float(jnp.max(jnp.abs(lx - lk))) < 0.3


def test_multi_token_decode_loop():
    """Decode 8 tokens sequentially == full forward on the whole sequence."""
    cfg, m, params = _mk("granite-3-8b")
    S, n_dec = 24, 8
    tokens, _ = _batch(cfg, m, S=S + n_dec)
    ctx = Ctx()
    logits_full, _ = m.apply(params, tokens, ctx)
    _, cache = m.prefill(params, tokens[:, :S], ctx, S + n_dec + 1)
    for i in range(n_dec):
        dl, cache = m.decode_step(params, tokens[:, S + i:S + i + 1], cache,
                                  ctx)
        err = float(jnp.max(jnp.abs(dl - logits_full[:, S + i])))
        assert err < 0.2, f"step {i}: {err}"


def test_local_attention_masks_long_range():
    """A local-attn model's logits at position t must not depend on tokens
    more than `window` behind t (MoE disabled: capacity routing couples
    tokens globally by design)."""
    cfg = get_config("mixtral-8x7b", smoke=True).replace(
        local_window=8, num_experts=0, experts_per_token=0)
    m = build_model(cfg)
    params = m.init(jax.random.PRNGKey(0))
    ctx = Ctx()
    t1 = jax.random.randint(jax.random.PRNGKey(1), (1, 64), 0, cfg.vocab_size)
    t2 = t1.at[:, :8].set((t1[:, :8] + 7) % cfg.vocab_size)
    l1, _ = m.apply(params, t1, ctx)
    l2, _ = m.apply(params, t2, ctx)
    # 3 layers x window 8 -> receptive field 24; position 63 sees >= 40 only
    np.testing.assert_allclose(np.asarray(l1[:, -1]), np.asarray(l2[:, -1]),
                               atol=1e-2)
