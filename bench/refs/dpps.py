"""Plain DPPS (Algorithm 1 of arXiv:2602.11544) over flat per-node rows.

State per node i: the push-sum numerator row s_i (the shared leaves
concatenated in wire order), the push-sum weight a_i, the local sensitivity
estimate S_i and the L1 norm of the noise it drew last round. One round:

  1. perturb      s_i' = s_i + eps_i                                (Eq. 7)
  2. sensitivity  S_i = 2C'(|s_i|_1 + |eps_i|_1)                 at t = 0
                  S_i = lam S_i + 2C'(|eps_i|_1 + lam g_n |n_i|_1)  after
                  S = max_i S_i                                     (Eq. 22)
  3. noise        s_i'' = s_i' + g_n n_i,  n_i ~ Laplace(0, S / b)   (Eq. 8)
  4. gossip       s <- W s'',  a <- W a                              (Eq. 9)
  5. sync         every ``sync_interval`` rounds: s_i <- mean_j s_j'',
                  a_i <- 1, S_i <- 2C' |mean_j s_j''|_1, |n_i|_1 <- 0

The Laplace draw is defined on 32-bit threefry bits, as the deployment
draws it: node i's row uses ``random.bits(split(key, N)[i], (d_s,))``; each
word's top 24 bits give u in [0, 1), c = u - 1/2, and
n = -(S/b) sign(c) log(max(1 - 2|c|, 1e-30)).

W is the configuration's graph as ``graphs/<kind>.py`` builds it.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


class RefState(NamedTuple):
    s: jax.Array        # (N, d_s) push-sum numerators
    a: jax.Array        # (N,) push-sum weights
    s_local: jax.Array  # (N,) local sensitivity estimates
    prev: jax.Array     # (N,) |n_i|_1 of the previous round's noise
    t: jax.Array        # () round counter


def init(s0: jax.Array) -> RefState:
    n = s0.shape[0]
    z = jnp.zeros((n,), jnp.float32)
    return RefState(s0.astype(jnp.float32), jnp.ones((n,), jnp.float32), z,
                    z, jnp.zeros((), jnp.int32))


def _bf16(x):
    """x rounded to bfloat16, kept in float32. ``reduce_precision`` and not
    a cast pair: XLA may drop an f32 -> bf16 -> f32 round trip as excess
    precision, on the TPU it does, and the control then is not the one
    stated."""
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


def mix(w: jax.Array, x: jax.Array, precision: str = "highest") -> jax.Array:
    """``w @ x`` in f32 (``highest``), or emulating a lower-precision MXU
    product: ``high`` sums three bf16 products (hi*hi + hi*lo + lo*hi),
    ``default`` takes one bf16 product; all accumulate in f32."""
    if precision == "highest":
        return jnp.dot(w, x, precision=HIGHEST)
    w_hi, x_hi = _bf16(w), _bf16(x)
    out = jnp.dot(w_hi, x_hi, precision=HIGHEST)
    if precision == "default":
        return out
    if precision != "high":
        raise ValueError(f"unknown mix precision {precision!r}")
    w_lo, x_lo = _bf16(w - w_hi), _bf16(x - x_hi)
    return (out + jnp.dot(w_hi, x_lo, precision=HIGHEST)
            + jnp.dot(w_lo, x_hi, precision=HIGHEST))


def laplace_from_bits(bits: jax.Array, scale) -> jax.Array:
    top24 = (bits >> 8).astype(jnp.int32)
    u = top24.astype(jnp.float32) * (1.0 / (1 << 24))
    c = u - 0.5
    mag = jnp.maximum(1.0 - 2.0 * jnp.abs(c), 1e-30)
    return -scale * jnp.sign(c) * jnp.log(mag)


def _noised(s, eps, key, scale, gamma_n):
    """Per node: s + eps + g_n n, |n|_1, and the node-summed g_n n (the
    last is what the comparison needs to read a gradient back out of a
    mixed state)."""
    n_nodes, d_s = s.shape
    keys = jax.random.split(key, n_nodes)

    def one(args):
        k, s_i, e_i = args
        noise = laplace_from_bits(jax.random.bits(k, (d_s,), jnp.uint32),
                                  scale)
        return s_i + e_i + gamma_n * noise, jnp.sum(jnp.abs(noise)), \
            gamma_n * noise

    def body(acc, args):
        out, l1, scaled = one(args)
        return acc + scaled, (out, l1)

    noise_sum, (s_noise, noise_l1) = jax.lax.scan(
        body, jnp.zeros((d_s,), jnp.float32), (keys, s, eps))
    return s_noise, noise_l1, noise_sum


def round_(state: RefState, eps: jax.Array | None, key: jax.Array, *,
           b: float, gamma_n: float, c_prime: float, lam: float,
           sync_interval: int, w: jax.Array, precision: str = "highest"):
    """One DPPS round -> (state, node-summed scaled noise of this round)."""
    s = state.s
    if eps is None:
        eps = jnp.zeros_like(s)
    eps_l1 = jnp.sum(jnp.abs(eps), axis=1)
    s_local = jax.lax.cond(
        state.t == 0,
        lambda: 2.0 * c_prime * (jnp.sum(jnp.abs(s), axis=1) + eps_l1),
        lambda: lam * state.s_local + 2.0 * c_prime * (
            eps_l1 + lam * gamma_n * state.prev))
    scale = jnp.max(s_local) / b
    s_noise, noise_l1, noise_sum = _noised(s, eps, key, scale, gamma_n)
    s_new = mix(w, s_noise, precision)
    a_new = mix(w, state.a[:, None], precision)[:, 0]
    prev = noise_l1
    if sync_interval > 0:
        def synced():
            mean = jnp.mean(s_noise, axis=0, keepdims=True)
            n = s.shape[0]
            return (jnp.broadcast_to(mean, s.shape), jnp.ones_like(a_new),
                    jnp.broadcast_to(2.0 * c_prime * jnp.sum(jnp.abs(mean)),
                                     (n,)),
                    jnp.zeros_like(noise_l1))

        s_new, a_new, s_local, prev = jax.lax.cond(
            (state.t + 1) % sync_interval == 0, synced,
            lambda: (s_new, a_new, s_local, prev))
    return RefState(s_new, a_new, s_local, prev, state.t + 1), noise_sum


@functools.partial(jax.jit, static_argnames=(
    "rounds", "b", "gamma_n", "c_prime", "lam", "sync_interval",
    "precision"))
def run(s0: jax.Array, key: jax.Array, w: jax.Array, *, rounds: int,
        b: float, gamma_n: float, c_prime: float, lam: float,
        sync_interval: int, precision: str = "highest") -> RefState:
    """``rounds`` rounds of pure consensus (no perturbation) from ``s0``;
    round t draws its noise from ``fold_in(key, t)``."""

    def body(st, _):
        st, _ = round_(st, None, jax.random.fold_in(key, st.t), b=b,
                       gamma_n=gamma_n, c_prime=c_prime, lam=lam,
                       sync_interval=sync_interval, w=w, precision=precision)
        return st, None

    final, _ = jax.lax.scan(body, init(s0), None, length=rounds)
    return final


def corrected(state: RefState) -> jax.Array:
    """y_i = s_i / a_i (Eq. 10)."""
    return state.s / state.a[:, None]
