"""Flash attention (forward) — blockwise online-softmax Pallas kernel.

Targets the §Roofline finding that the 32k prefill shapes are memory-bound
on attention traffic: the naive path materializes (S, S) scores per head in
HBM; this kernel streams K/V blocks through VMEM with running (m, l)
softmax statistics, so HBM traffic is O(S·D) instead of O(S²).

Layout: q, k, v as (H, S, D) / (K_heads, S, D); GQA maps query head h to
kv head h // group. Grid (h, iq, ik) with ik innermost; VMEM scratch keeps
the (BQ, D) accumulator and the (BQ,) running max/denominator between ik
steps. Causal and sliding-window masks are applied block-wise.

Forward-only (prefill/serving); training uses the jnp path (a fused
backward is future work — see DESIGN.md). Validated in interpret mode
against ref.flash_attention across shape/window sweeps.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BQ = 128
BK = 128
_NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, spec_ref, o_ref, acc_ref, m_ref, l_ref,
            *, nk: int, group: int):
    ik = pl.program_id(2)
    iq = pl.program_id(1)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0].astype(jnp.float32)          # (BQ, D)
    k = k_ref[0].astype(jnp.float32)          # (BK, D)
    v = v_ref[0].astype(jnp.float32)          # (BK, D)
    scale = spec_ref[0, 0]
    window = spec_ref[0, 1]                    # < 0 means global

    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale  # (BQ, BK)

    q_pos = iq * BQ + jax.lax.broadcasted_iota(jnp.int32, (BQ, BK), 0)
    k_pos = ik * BK + jax.lax.broadcasted_iota(jnp.int32, (BQ, BK), 1)
    mask = (q_pos >= k_pos) & ((window < 0) | ((q_pos - k_pos) < window))
    s = jnp.where(mask, s, _NEG_INF)

    m_prev = m_ref[...]                        # (BQ,)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new[:, None])
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1)
    acc_ref[...] = acc_ref[...] * alpha[:, None] + jnp.dot(
        p, v, preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(ik == nk - 1)
    def _finalize():
        denom = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / denom[:, None]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("group", "window", "interpret"))
def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                    group: int = 1, window: int | None = None,
                    window_dynamic=None, interpret: bool) -> jnp.ndarray:
    """q: (H, S, D); k, v: (H // group, S, D). Causal; optional sliding
    window (static ``window`` or traced ``window_dynamic``; < 0 == global).
    S must be a multiple of BQ (pad upstream). Returns (H, S, D)."""
    h, s, d = q.shape
    kh = k.shape[0]
    assert h == kh * group, (h, kh, group)
    assert s % BQ == 0 and s % BK == 0, s
    nq, nk = s // BQ, s // BK
    scale = 1.0 / (d ** 0.5)
    if window_dynamic is not None:
        win = jnp.asarray(window_dynamic, jnp.float32)
    else:
        win = jnp.asarray(-1.0 if window is None else float(window), jnp.float32)
    spec = jnp.stack([jnp.asarray(scale, jnp.float32), win]).reshape(1, 2)

    kernel = functools.partial(_kernel, nk=nk, group=group)
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((h, s, d), q.dtype),
        grid=(h, nq, nk),
        in_specs=[
            pl.BlockSpec((1, BQ, d), lambda ih, iq, ik: (ih, iq, 0)),
            pl.BlockSpec((1, BK, d), lambda ih, iq, ik, g=group: (ih // g, ik, 0)),
            pl.BlockSpec((1, BK, d), lambda ih, iq, ik, g=group: (ih // g, ik, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),     # (scale, window)
        ],
        out_specs=pl.BlockSpec((1, BQ, d), lambda ih, iq, ik: (ih, iq, 0)),
        scratch_shapes=[
            pltpu.VMEM((BQ, d), jnp.float32),   # softmax-weighted accumulator
            pltpu.VMEM((BQ,), jnp.float32),     # running max m
            pltpu.VMEM((BQ,), jnp.float32),     # running denominator l
        ],
        interpret=interpret,
    )(q, k, v, spec)
