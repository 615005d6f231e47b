"""Recurrent sequence mixers: mLSTM + sLSTM (xLSTM) and Mamba2 (SSD).

All mixers expose:
  init_*(key, d_model, ...)                  -> params
  *_seq(params, x)                           -> (y, final_state)   # training
  *_step(params, x_t, state)                 -> (y_t, new_state)   # decode

The xLSTM cells train with ``lax.scan`` over time (the recurrent form);
Mamba2 trains with the chunked SSD scan (``ssd_chunked``) and decodes with
its recurrence. Decode is O(1) state per token, which is what makes the
ssm / hybrid architectures long_500k-eligible.

xLSTM departures from the paper: the mLSTM block's depthwise conv is
omitted; gate biases init to small constants for stable exp-gating.

Mamba2 follows ``transformers``' ``Zamba2MambaMixer.torch_forward`` (the
slow path): in_proj to z, x, B, C, dt (no gated-MLP part, d_mlp = 0); a
causal depthwise conv (with bias) and SiLU over [x, B, C]; B/C in groups
shared by their heads; dt = softplus(dt + dt_bias) floored at
time_step_min as that path floors it (no time_step_limit); A = -exp(A_log);
the D skip; RMSNormGated(y, z) over each group's channels, eps 1e-5; out
projection. Departures: the SSD chunk length is min(chunk_size, L) (the
same sums; the slow path pads to whole 256-token chunks); the state
between chunks is one decay-matrix product where the slow path builds the
matrix from a segment sum, and it sums over the source chunk: the slow
path of transformers 4.57.6 sums over the target chunk, which is wrong
beyond one chunk, so this follows the recurrence that its cached decode
runs (tests/bench/test_bench_zamba2_refs.py).
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.models.layers import dense_init

__all__ = [
    "init_mlstm", "mlstm_seq", "mlstm_step", "mlstm_state",
    "init_slstm", "slstm_seq", "slstm_step", "slstm_state",
    "Mamba2Dims", "init_mamba2", "mamba2_seq", "mamba2_step", "mamba2_state",
    "ssd_chunked",
]


# ---------------------------------------------------------------------------
# mLSTM (matrix-memory LSTM; xLSTM arXiv:2405.04517 Eq. 19-27)
# ---------------------------------------------------------------------------

def init_mlstm(key: jax.Array, d_model: int, n_heads: int, proj_factor: float = 2.0,
               dtype=jnp.float32) -> dict:
    d_inner = int(d_model * proj_factor)
    assert d_inner % n_heads == 0
    ku, kq, kk, kv, kg, ko, kd = jax.random.split(key, 7)
    hd = d_inner // n_heads
    return {
        "w_up": dense_init(ku, (d_model, d_inner), dtype),
        "w_q": dense_init(kq, (d_inner, d_inner), dtype),
        "w_k": dense_init(kk, (d_inner, d_inner), dtype),
        "w_v": dense_init(kv, (d_inner, d_inner), dtype),
        # scalar i/f gates per head + vector o gate
        "w_if": dense_init(kg, (d_inner, 2 * n_heads), dtype),
        "b_if": jnp.concatenate([
            jnp.full((n_heads,), -3.0, dtype),   # input gate starts small
            jnp.full((n_heads,), 3.0, dtype),    # forget gate starts open
        ]),
        "w_o": dense_init(ko, (d_model, d_inner), dtype),
        "w_down": dense_init(kd, (d_inner, d_model), dtype),
    }


def mlstm_state(batch: int, d_model: int, n_heads: int, proj_factor: float = 2.0,
                dtype=jnp.float32) -> dict:
    d_inner = int(d_model * proj_factor)
    hd = d_inner // n_heads
    return {
        "C": jnp.zeros((batch, n_heads, hd, hd), dtype),
        "n": jnp.zeros((batch, n_heads, hd), dtype),
        "m": jnp.full((batch, n_heads), -1e30, dtype),
    }


def _mlstm_gates_qkv(params: dict, x: jnp.ndarray, n_heads: int):
    """x: (B, S, d_model) -> per-step q,k,v (B,S,H,hd), i/f pre-acts (B,S,H), o (B,S,H,hd)."""
    h = n_heads
    hd = params["w_q"].shape[1] // h
    u = x @ params["w_up"]                       # (B,S,d_inner)
    q = (u @ params["w_q"]).reshape(u.shape[:-1] + (h, hd))
    k = (u @ params["w_k"]).reshape(u.shape[:-1] + (h, hd)) / jnp.sqrt(jnp.asarray(hd, x.dtype))
    v = (u @ params["w_v"]).reshape(u.shape[:-1] + (h, hd))
    gif = (u @ params["w_if"] + params["b_if"]).astype(jnp.float32)
    i_pre, f_pre = gif[..., :h], gif[..., h:]
    o = jax.nn.sigmoid((x @ params["w_o"]).astype(jnp.float32)).astype(x.dtype)
    return q, k, v, i_pre, f_pre, o, u


def _mlstm_cell(carry, inp):
    """One stabilized mLSTM step. carry: (C,n,m); inp: (q,k,v,i_pre,f_pre)."""
    C, n, m = carry
    q, k, v, i_pre, f_pre = inp
    f_log = jax.nn.log_sigmoid(f_pre)                         # (B,H)
    m_new = jnp.maximum(f_log + m, i_pre)
    f_act = jnp.exp(f_log + m - m_new)[..., None, None]
    i_act = jnp.exp(i_pre - m_new)[..., None, None]
    kf = k.astype(jnp.float32); vf = v.astype(jnp.float32); qf = q.astype(jnp.float32)
    C_new = f_act * C + i_act * (vf[..., :, None] * kf[..., None, :])  # (B,H,hd_v,hd_k)
    n_new = f_act[..., 0] * n + i_act[..., 0] * kf
    num = jnp.einsum("bhvk,bhk->bhv", C_new, qf)
    den = jnp.maximum(jnp.abs(jnp.einsum("bhk,bhk->bh", n_new, qf)), 1.0)
    h_t = num / den[..., None]                                # (B,H,hd)
    return (C_new, n_new, m_new), h_t


def mlstm_seq(params: dict, x: jnp.ndarray, *, n_heads: int, state: dict | None = None):
    b, s, d = x.shape
    if state is None:
        state = mlstm_state(b, d, n_heads, params["w_up"].shape[1] / d)
    q, k, v, i_pre, f_pre, o, _ = _mlstm_gates_qkv(params, x, n_heads)
    # time-major scan
    inputs = tuple(jnp.moveaxis(t, 1, 0) for t in (q, k, v, i_pre, f_pre))
    carry = (state["C"].astype(jnp.float32), state["n"].astype(jnp.float32),
             state["m"].astype(jnp.float32))
    carry, hs = jax.lax.scan(_mlstm_cell, carry, inputs)
    h = jnp.moveaxis(hs, 0, 1).reshape(b, s, -1).astype(x.dtype)  # (B,S,d_inner)
    y = (o * h) @ params["w_down"]
    new_state = {"C": carry[0], "n": carry[1], "m": carry[2]}
    return y, new_state


def mlstm_step(params: dict, x: jnp.ndarray, state: dict, *, n_heads: int):
    """x: (B, 1, d_model)."""
    q, k, v, i_pre, f_pre, o, _ = _mlstm_gates_qkv(params, x, n_heads)
    carry = (state["C"].astype(jnp.float32), state["n"].astype(jnp.float32),
             state["m"].astype(jnp.float32))
    carry, h = _mlstm_cell(carry, (q[:, 0], k[:, 0], v[:, 0], i_pre[:, 0], f_pre[:, 0]))
    b = x.shape[0]
    h = h.reshape(b, 1, -1).astype(x.dtype)
    y = (o * h) @ params["w_down"]
    return y, {"C": carry[0], "n": carry[1], "m": carry[2]}


# ---------------------------------------------------------------------------
# sLSTM (scalar-memory LSTM with recurrent gate connections)
# ---------------------------------------------------------------------------

def init_slstm(key: jax.Array, d_model: int, dtype=jnp.float32) -> dict:
    kw, kr = jax.random.split(key)
    return {
        "w": dense_init(kw, (d_model, 4 * d_model), dtype),     # z,i,f,o pre-acts
        "r": dense_init(kr, (d_model, 4 * d_model), dtype),     # recurrent h -> gates
        "b": jnp.concatenate([
            jnp.zeros((d_model,), dtype),
            jnp.full((d_model,), -3.0, dtype),
            jnp.full((d_model,), 3.0, dtype),
            jnp.zeros((d_model,), dtype),
        ]),
    }


def slstm_state(batch: int, d_model: int, dtype=jnp.float32) -> dict:
    return {
        "c": jnp.zeros((batch, d_model), dtype),
        "n": jnp.zeros((batch, d_model), dtype),
        "m": jnp.full((batch, d_model), -1e30, dtype),
        "h": jnp.zeros((batch, d_model), dtype),
    }


def _slstm_cell(params, carry, wx_t):
    c, n, m, h = carry
    d = c.shape[-1]
    pre = (wx_t + h @ params["r"].astype(jnp.float32)).astype(jnp.float32)
    z_pre, i_pre, f_pre, o_pre = jnp.split(pre, 4, axis=-1)
    z = jnp.tanh(z_pre)
    f_log = jax.nn.log_sigmoid(f_pre)
    m_new = jnp.maximum(f_log + m, i_pre)
    f_act = jnp.exp(f_log + m - m_new)
    i_act = jnp.exp(i_pre - m_new)
    c_new = f_act * c + i_act * z
    n_new = f_act * n + i_act
    h_new = jax.nn.sigmoid(o_pre) * c_new / jnp.maximum(n_new, 1.0)
    return (c_new, n_new, m_new, h_new), h_new


def slstm_seq(params: dict, x: jnp.ndarray, state: dict | None = None):
    b, s, d = x.shape
    if state is None:
        state = slstm_state(b, d)
    wx = (x @ params["w"] + params["b"]).astype(jnp.float32)  # (B,S,4d)
    carry = tuple(state[k].astype(jnp.float32) for k in ("c", "n", "m", "h"))
    carry, hs = jax.lax.scan(
        lambda c, t: _slstm_cell(params, c, t), carry, jnp.moveaxis(wx, 1, 0)
    )
    y = jnp.moveaxis(hs, 0, 1).astype(x.dtype)
    new_state = dict(zip(("c", "n", "m", "h"), carry))
    return y, new_state


def slstm_step(params: dict, x: jnp.ndarray, state: dict):
    wx = (x[:, 0] @ params["w"] + params["b"]).astype(jnp.float32)
    carry = tuple(state[k].astype(jnp.float32) for k in ("c", "n", "m", "h"))
    carry, h = _slstm_cell(params, carry, wx)
    return h[:, None].astype(x.dtype), dict(zip(("c", "n", "m", "h"), carry))


# ---------------------------------------------------------------------------
# Mamba2 (state-space duality layer, arXiv:2405.21060), as Zamba2 runs it
# ---------------------------------------------------------------------------

class Mamba2Dims(NamedTuple):
    """What one Mamba2 mixer holds: ``n_heads`` heads of ``head_dim`` fed by
    ``n_groups`` B/C groups of ``d_state``, a causal depthwise conv of
    ``d_conv`` taps, SSD chunks of ``chunk_size``; dt floored at ``dt_min``.
    A tensor-parallel share holds whole groups, with their heads."""

    n_heads: int
    head_dim: int
    n_groups: int
    d_state: int
    d_conv: int = 4
    chunk_size: int = 256
    dt_min: float = 1e-3

    @property
    def d_inner(self) -> int:
        return self.n_heads * self.head_dim

    @property
    def conv_dim(self) -> int:
        return self.d_inner + 2 * self.n_groups * self.d_state


def init_mamba2(key: jax.Array, d_model: int, dims: Mamba2Dims,
                dtype=jnp.float32) -> dict:
    """``in_proj`` columns are [z | x | B | C | dt]; dt_bias is the inverse
    softplus of a log-uniform dt in [1e-3, 0.1] and A_log = log(1..H), as
    the published initializer sets them."""
    ki, kc, ko, kt = jax.random.split(key, 4)
    di, nh = dims.d_inner, dims.n_heads
    dt = jnp.exp(jax.random.uniform(kt, (nh,), jnp.float32)
                 * (jnp.log(0.1) - jnp.log(1e-3)) + jnp.log(1e-3))
    dt = jnp.maximum(dt, 1e-4)
    return {
        "in_proj": dense_init(ki, (d_model, di + dims.conv_dim + nh), dtype),
        "conv_w": dense_init(kc, (dims.d_conv, dims.conv_dim), dtype),
        "conv_b": jnp.zeros((dims.conv_dim,), dtype),
        "dt_bias": (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype),
        "a_log": jnp.log(jnp.arange(1, nh + 1, dtype=jnp.float32)).astype(dtype),
        "d_skip": jnp.ones((nh,), dtype),
        "norm": jnp.ones((di,), dtype),
        "out_proj": dense_init(ko, (di, d_model), dtype),
    }


def mamba2_state(batch: int, dims: Mamba2Dims, dtype=jnp.float32) -> dict:
    """``ssm`` (B, H, P, N) and the conv's last ``d_conv - 1`` inputs."""
    return {"ssm": jnp.zeros((batch, dims.n_heads, dims.head_dim,
                              dims.d_state), dtype),
            "conv": jnp.zeros((batch, dims.d_conv - 1, dims.conv_dim), dtype)}


def _ssd_chunk(xdt, acum, b, c):
    """One chunk: its own part of y, (C B^T o L) (dt x), and the state it
    adds by its end. xdt (B, T, G, H/G, P), acum (B, T, H) the running sum
    of dt A inside the chunk, b/c (B, T, G, N)."""
    bsz, t, ng, hg, _ = xdt.shape
    ah = jnp.moveaxis(acum, 1, 2)                          # (B, H, T)
    causal = jnp.tril(jnp.ones((t, t), bool))
    seg = jnp.exp(jnp.where(causal, ah[..., :, None] - ah[..., None, :],
                            -jnp.inf)).reshape(bsz, ng, hg, t, t)
    cb = jnp.einsum("bign,bjgn->bgij", c, b)
    y = jnp.einsum("bgeij,bjgep->bigep", seg * cb[:, :, None], xdt)
    to_end = jnp.exp(acum[:, -1:] - acum).reshape(bsz, t, ng, hg)
    return y, jnp.einsum("bjgn,bjge,bjgep->bgepn", b, to_end, xdt)


def ssd_chunked(x, dt, a, b, c, chunk_size: int, h0=None):
    """The SSD scan in chunks: y_t = sum_{s<=t} C_t.B_s exp(sum_{s<u<=t} dt_u A)
    dt_s x_s, and the final state.

    x (B, L, H, P), dt (B, L, H), a (H,) negative, b/c (B, L, G, N) with head
    h reading group h // (H / G); h0 (B, H, P, N) or None. Inside a chunk
    the (C B^T o L) X matmul (``_ssd_chunk``, one chunk at a time and
    recomputed for the backward pass, so only one chunk's (T, T) decays
    are held); across chunks a recurrence on the chunk states, as one
    matmul over the (chunks + 1)^2 decay matrix. The sequence is
    zero-padded to whole chunks (dt 0 neither decays nor adds).
    """
    bsz, seq, nh, hp = x.shape
    ng, n = b.shape[2], b.shape[3]
    hg = nh // ng
    t = min(chunk_size, seq)
    pad = (-seq) % t
    nc = (seq + pad) // t
    f32 = jnp.float32

    def chunks(v):
        v = jnp.pad(v.astype(f32), [(0, 0), (0, pad)] + [(0, 0)] * (v.ndim - 2))
        return v.reshape((bsz, nc, t) + v.shape[2:])

    xdt = chunks(x * dt[..., None]).reshape(bsz, nc, t, ng, hg, hp)
    acum = jnp.cumsum(chunks(dt * a), axis=2)             # (B, c, T, H)
    b, c = chunks(b), chunks(c)                           # (B, c, T, G, N)
    y_diag, states = jax.lax.map(
        lambda v: jax.checkpoint(_ssd_chunk)(*v),
        tuple(jnp.moveaxis(v, 1, 0) for v in (xdt, acum, b, c)))
    y_diag = jnp.moveaxis(y_diag, 0, 1)
    states = jnp.moveaxis(states, 0, 1).reshape(bsz, nc, nh, hp, n)
    if h0 is None:
        h0 = jnp.zeros((bsz, nh, hp, n), f32)
    src = jnp.concatenate([h0.astype(f32)[:, None], states], axis=1)
    cs = jnp.cumsum(jnp.pad(acum[:, :, -1], [(0, 0), (1, 0), (0, 0)]), axis=1)
    keep = jnp.tril(jnp.ones((nc + 1, nc + 1), bool))[..., None]
    decay = jnp.exp(jnp.where(keep, cs[:, :, None] - cs[:, None], -jnp.inf))
    enter = jnp.einsum("bmkh,bkhpn->bmhpn", decay, src)  # state entering m
    prev = enter[:, :nc].reshape(bsz, nc, ng, hg, hp, n)
    y_off = (jnp.einsum("bcign,bcgepn->bcigep", c, prev)
             * jnp.exp(acum).reshape(bsz, nc, t, ng, hg)[..., None])
    y = (y_diag + y_off).reshape(bsz, nc * t, nh, hp)[:, :seq]
    return y, enter[:, nc]


def _mamba2_in(params, x, dims: Mamba2Dims):
    """in_proj -> (z, pre-conv [x | B | C], dt before its bias)."""
    zxbcdt = x @ params["in_proj"]
    di, cd = dims.d_inner, dims.conv_dim
    return zxbcdt[..., :di], zxbcdt[..., di:di + cd], zxbcdt[..., di + cd:]


def _mamba2_ssm_in(params, xbc, dt, dims: Mamba2Dims):
    """Post-conv [x | B | C] and raw dt -> per-head x, grouped B and C,
    dt = max(softplus(dt + dt_bias), dt_min), A = -exp(A_log)."""
    lead = xbc.shape[:-1]
    di, gn = dims.d_inner, dims.n_groups * dims.d_state
    xs = xbc[..., :di].reshape(lead + (dims.n_heads, dims.head_dim))
    bm = xbc[..., di:di + gn].reshape(lead + (dims.n_groups, dims.d_state))
    cm = xbc[..., di + gn:].reshape(lead + (dims.n_groups, dims.d_state))
    dt = jnp.maximum(jax.nn.softplus(
        dt.astype(jnp.float32) + params["dt_bias"].astype(jnp.float32)),
        dims.dt_min)
    a = -jnp.exp(params["a_log"].astype(jnp.float32))
    return xs.astype(jnp.float32), bm, cm, dt, a


def _mamba2_out(params, y, z, dims: Mamba2Dims, out_dtype):
    """y * silu(z), RMS-normed over each B/C group's channels (eps 1e-5),
    scaled, then out_proj."""
    y = y * jax.nn.silu(z.astype(jnp.float32))
    yg = y.reshape(y.shape[:-1] + (dims.n_groups, -1))
    yg = yg * jax.lax.rsqrt(jnp.mean(yg * yg, axis=-1, keepdims=True) + 1e-5)
    y = yg.reshape(y.shape) * params["norm"].astype(jnp.float32)
    return y.astype(out_dtype) @ params["out_proj"]


def mamba2_seq(params: dict, x: jnp.ndarray, dims: Mamba2Dims,
               state: dict | None = None):
    """x (B, L, d_model) -> (out, state after the last token). Training and
    prefill: the conv over the sequence, then the chunked SSD."""
    bsz, seq, _ = x.shape
    if state is None:
        state = mamba2_state(bsz, dims)
    z, xbc, dt = _mamba2_in(params, x, dims)
    xpad = jnp.concatenate([state["conv"].astype(xbc.dtype), xbc], axis=1)
    conv = params["conv_b"] + sum(
        xpad[:, k:k + seq] * params["conv_w"][k] for k in range(dims.d_conv))
    xs, bm, cm, dt, a = _mamba2_ssm_in(params, jax.nn.silu(conv), dt, dims)
    y, h = ssd_chunked(xs, dt, a, bm, cm, dims.chunk_size, state["ssm"])
    y = y + params["d_skip"].astype(jnp.float32)[:, None] * xs
    out = _mamba2_out(params, y.reshape(bsz, seq, -1), z, dims, x.dtype)
    return out, {"ssm": h, "conv": xpad[:, seq:]}


def mamba2_step(params: dict, x: jnp.ndarray, dims: Mamba2Dims, state: dict):
    """x (B, 1, d_model): one token through the conv window and the
    recurrent state (decode)."""
    z, xbc, dt = _mamba2_in(params, x, dims)
    window = jnp.concatenate([state["conv"].astype(xbc.dtype), xbc], axis=1)
    conv = params["conv_b"] + jnp.sum(window * params["conv_w"], axis=1)
    xs, bm, cm, dt, a = _mamba2_ssm_in(params, jax.nn.silu(conv), dt[:, 0],
                                       dims)
    rep = dims.n_heads // dims.n_groups
    bh = jnp.repeat(bm.astype(jnp.float32), rep, axis=1)     # (B, H, N)
    ch = jnp.repeat(cm.astype(jnp.float32), rep, axis=1)
    h = (state["ssm"].astype(jnp.float32) * jnp.exp(dt * a)[..., None, None]
         + (dt[..., None] * xs)[..., None] * bh[:, :, None, :])
    y = jnp.einsum("bhpn,bhn->bhp", h, ch)
    y = y + params["d_skip"].astype(jnp.float32)[:, None] * xs
    out = _mamba2_out(params, y.reshape(x.shape[0], 1, -1), z, dims, x.dtype)
    return out, {"ssm": h, "conv": window[:, 1:]}
