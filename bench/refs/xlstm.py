"""Plain xLSTM language model (arXiv:2405.04517): forward, loss, gradients.

Stack: token embedding -> ``n_units`` x [``mlstm_per_unit`` pre-norm mLSTM
blocks, one pre-norm sLSTM block], each block added to the residual stream
-> RMS norm -> head tied to the embedding -> next-token cross entropy,
averaged over every predicted position.

mLSTM block (matrix memory, stabilised exponential gating, Eqs. 19-27):
u = x W_up; per head q, k/sqrt(d_h), v from u; scalar input and forget
pre-activations from u W_if + b_if; o = sigmoid(x W_o). Per step
  m_t = max(log sigmoid(f) + m_{t-1}, i),  f' = exp(log sigmoid(f) + m_{t-1} - m_t),
  i' = exp(i - m_t),  C_t = f' C_{t-1} + i' v k^T,  n_t = f' n_{t-1} + i' k,
  h_t = C_t q / max(|n_t . q|, 1);
the block outputs (o * h) W_down.

sLSTM block (scalar memory with recurrent gates): pre = x W + b + h_{t-1} R
split into z, i, f, o; z = tanh, the same stabilised gates, c_t = f' c + i' z,
n_t = f' n + i', h_t = sigmoid(o) c_t / max(n_t, 1).

Departures from the paper's block design, shared with the deployment this
benchmark runs: no causal convolution in either block, no group norm on the
heads, and no up/down projection around the sLSTM. RMS norms use eps 1e-6.

``dtype=float32`` computes at ``Precision.HIGHEST``; ``dtype=bfloat16`` is
the control: parameters, activations and recurrent states in bfloat16, the
cross entropy reduced in float32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def _rms(x, scale, eps):
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)
            ).astype(x.dtype)


def _mm(x, w):
    return jnp.matmul(x, w, precision=HIGHEST)


def _mlstm(p, x, n_heads):
    b, s, _ = x.shape
    u = _mm(x, p["w_up"])
    di = u.shape[-1]
    hd = di // n_heads
    q = _mm(u, p["w_q"]).reshape(b, s, n_heads, hd)
    k = _mm(u, p["w_k"]).reshape(b, s, n_heads, hd) / jnp.sqrt(
        jnp.asarray(hd, x.dtype))
    v = _mm(u, p["w_v"]).reshape(b, s, n_heads, hd)
    gates = _mm(u, p["w_if"]) + p["b_if"]
    i_pre, f_pre = gates[..., :n_heads], gates[..., n_heads:]
    o = jax.nn.sigmoid(_mm(x, p["w_o"]))
    dt = x.dtype

    def step(carry, inp):
        c, n, m = carry
        q_t, k_t, v_t, i_t, f_t = inp
        f_log = jax.nn.log_sigmoid(f_t)
        m_new = jnp.maximum(f_log + m, i_t)
        f_act = jnp.exp(f_log + m - m_new)
        i_act = jnp.exp(i_t - m_new)
        c = (f_act[..., None, None] * c
             + i_act[..., None, None] * (v_t[..., :, None] * k_t[..., None, :]))
        n = f_act[..., None] * n + i_act[..., None] * k_t
        num = jnp.einsum("bhvk,bhk->bhv", c, q_t, precision=HIGHEST)
        den = jnp.maximum(jnp.abs(jnp.einsum("bhk,bhk->bh", n, q_t,
                                             precision=HIGHEST)), 1.0)
        return (c, n, m_new), num / den[..., None]

    carry = (jnp.zeros((b, n_heads, hd, hd), dt),
             jnp.zeros((b, n_heads, hd), dt),
             jnp.full((b, n_heads), -1e30, dt))
    seq = tuple(jnp.moveaxis(a, 1, 0) for a in (q, k, v, i_pre, f_pre))
    _, hs = jax.lax.scan(step, carry, seq)
    h = jnp.moveaxis(hs, 0, 1).reshape(b, s, di)
    return _mm(o * h, p["w_down"])


def _slstm(p, x):
    b, s, d = x.shape
    wx = _mm(x, p["w"]) + p["b"]
    r = p["r"]
    dt = x.dtype

    def step(carry, wx_t):
        c, n, m, h = carry
        pre = wx_t + _mm(h, r)
        z_pre, i_pre, f_pre, o_pre = jnp.split(pre, 4, axis=-1)
        z = jnp.tanh(z_pre)
        f_log = jax.nn.log_sigmoid(f_pre)
        m_new = jnp.maximum(f_log + m, i_pre)
        f_act = jnp.exp(f_log + m - m_new)
        i_act = jnp.exp(i_pre - m_new)
        c = f_act * c + i_act * z
        n = f_act * n + i_act
        h = jax.nn.sigmoid(o_pre) * c / jnp.maximum(n, 1.0)
        return (c, n, m_new, h), h

    zeros = jnp.zeros((b, d), dt)
    carry = (zeros, zeros, jnp.full((b, d), -1e30, dt), zeros)
    _, hs = jax.lax.scan(step, carry, jnp.moveaxis(wx, 1, 0))
    return jnp.moveaxis(hs, 0, 1)


def loss(params: dict, tokens: jax.Array, *, model: dict,
         dtype=jnp.float32) -> jax.Array:
    """Mean next-token cross entropy of one node's (batch, seq) tokens."""
    p = jax.tree_util.tree_map(lambda x: x.astype(dtype), params)
    eps = model.get("norm_eps", 1e-6)
    heads = model["n_heads"]
    g = p["group_0"]
    x = p["embed"][tokens]

    mblock = jax.checkpoint(
        lambda x, cell, scale: x + _mlstm(cell, _rms(x, scale, eps), heads))
    sblock = jax.checkpoint(
        lambda x, cell, scale: x + _slstm(cell, _rms(x, scale, eps)))
    for u in range(model["n_units"]):
        for m in range(model["mlstm_per_unit"]):
            cell = jax.tree_util.tree_map(lambda a: a[u, m], g["mlstm"]["cell"])
            x = mblock(x, cell, g["mlstm"]["ln"]["scale"][u, m])
        cell = jax.tree_util.tree_map(lambda a: a[u], g["slstm"]["cell"])
        x = sblock(x, cell, g["slstm"]["ln"]["scale"][u])
    x = _rms(x, p["final_ln"]["scale"], eps)
    logits = jnp.einsum("bsd,vd->bsv", x[:, :-1], p["embed"],
                        precision=HIGHEST).astype(jnp.float32)
    targets = tokens[:, 1:]
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - picked)


@functools.partial(jax.jit, static_argnames=("model_items", "dtype"))
def _value_and_grad(params, tokens, *, model_items, dtype):
    return jax.value_and_grad(loss)(params, tokens, model=dict(model_items),
                                    dtype=dtype)


def value_and_grad(params: dict, tokens: jax.Array, *, model: dict,
                   dtype=jnp.float32):
    """(loss, gradient tree) of one node, jitted per model and dtype."""
    return _value_and_grad(params, tokens,
                           model_items=tuple(sorted(model.items())),
                           dtype=jnp.dtype(dtype).name)
