"""Seeded synthetic token stream for the training cells.

A copy of the program's ``SyntheticLMStream`` (a low-rank first-order Markov
chain over the vocabulary, with a per-node temperature so the nodes' data
are not identically distributed), kept here so the yardstick cannot move
with the program. Its tables are drawn on the device from the seed, and one
jitted call makes a step's (nodes, batch, seq) tokens.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class TokenStream:
    vocab_size: int
    seq_len: int
    n_nodes: int
    per_node_batch: int
    markov_rank: int = 64
    node_skew: float = 0.5

    def tables(self, key: jax.Array) -> dict:
        ke, kc, kt = jax.random.split(key, 3)
        v, r = self.vocab_size, min(self.markov_rank, self.vocab_size)
        return {
            "emit": jax.random.normal(ke, (r, v), jnp.float32) * 2.0,
            "ctx": jax.random.normal(kc, (v, r), jnp.float32),
            "temp": 1.0 + self.node_skew * jax.random.uniform(
                kt, (self.n_nodes,), jnp.float32, -1.0, 1.0),
        }

    def _sample_node(self, tables, key, temp):
        def step(tok, k):
            logits = tables["ctx"][tok] @ tables["emit"] / temp
            nxt = jax.random.categorical(k, logits, axis=-1)
            return nxt, nxt

        k0, kseq = jax.random.split(key)
        tok0 = jax.random.randint(k0, (self.per_node_batch,), 0,
                                  self.vocab_size)
        keys = jax.random.split(kseq, self.seq_len - 1)
        _, rest = jax.lax.scan(step, tok0, keys)
        return jnp.concatenate([tok0[None], rest], axis=0).T

    def batch(self, tables, key: jax.Array) -> jax.Array:
        """(n_nodes, per_node_batch, seq_len) int32 tokens for one step."""
        keys = jax.random.split(key, self.n_nodes)
        toks = jax.vmap(self._sample_node, in_axes=(None, 0, 0))(
            tables, keys, tables["temp"])
        return toks.astype(jnp.int32)


def make_batcher(stream: TokenStream, key: jax.Array):
    """``batch_at(t)`` -> the step-``t`` token batch, on the device.

    The tables are built once; each step's tokens depend only on the key
    and ``t``, so every seed draws the same sizes and a different stream.
    """
    tables = jax.jit(stream.tables)(jax.random.fold_in(key, 0))
    sample = jax.jit(stream.batch)
    step_key = jax.random.fold_in(key, 1)

    def batch_at(t: int) -> jax.Array:
        return sample(tables, jax.random.fold_in(step_key, t))

    return batch_at
