"""Training batches from ``--seed``, made on the device during set-up.

The generator is the seeded Markov chain of the program's
``repro.train.data`` (banded jumps, a copied motif in the second half),
kept here so that the yardstick cannot move with the program; its
parameters come from the traffic file.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench.weights import DATA, base_key


def markov_tokens(key, batch, seq, vocab, jumps, probs):
    k1, k2 = jax.random.split(key)
    start = jax.random.randint(k1, (batch, 1), 0, vocab)
    pick = jax.random.categorical(k2, jnp.log(jnp.asarray(probs)),
                                  shape=(batch, seq))
    steps = jnp.asarray(jumps)[pick]
    return ((start + jnp.cumsum(steps, axis=1)) % vocab).astype(jnp.int32)


def batch(traffic: dict, vocab: int, words, index):
    """Batch ``index`` of the run: {tokens, labels}, each (batch, seq_len)."""
    key = jax.random.fold_in(base_key(words, DATA), index)
    b, s = traffic["batch"], traffic["seq_len"]
    toks = markov_tokens(key, b, s + 1, vocab, traffic["jumps"],
                         traffic["jump_probs"])
    motif = min(traffic["motif_len"], s // 4)
    if motif >= 4:
        toks = jax.lax.dynamic_update_slice(toks, toks[:, :motif], (0, s // 2))
    return {"tokens": toks[:, :-1], "labels": toks[:, 1:]}
