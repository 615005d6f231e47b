"""Plain Zamba2 language model (arXiv:2411.15242): forward, loss, gradients.

The equations of ``transformers``' ``modeling_zamba2`` (slow path), written
out again for one tensor-parallel share (``benchlib.zamba2_params.held``):

  emb = E[tokens];  h = emb
  layer l:  h <- h + Mamba2_l(rms_l(h + u_l)),  u_l = 0 in a mamba layer;
  k-th hybrid layer, tied block j = k mod num_mem_blocks:
    t   = rms_in_j(concat(h, emb))
    a   = rms_ff_j(o_j(softmax(q k^T (head_dim/2)^-0.5 + causal) v)),
          q, k = RoPE(t W_q), RoPE(t W_k) (rotate-half, theta), v = t W_v
    u_l = linear_k(down_j(gelu(g) * up)),  [g | up] = a W_gu + (a A_k) B_k
  Mamba2: [z | xBC | dt] = x W_in;  xBC <- silu(causal depthwise conv + b);
    dt = max(softplus(dt + dt_bias), time_step_min);  A = -exp(A_log);
    per token  H_t = exp(dt_t A) H_{t-1} + dt_t x_t B_t^T  (B, C of the
    head's group),  y_t = H_t C_t + D x_t;
    out = W_out(norm * rms_groups(y * silu(z), eps 1e-5))
  logits = rms_final(h) E^T over the share's vocabulary rows; next-token
  cross entropy averaged over every predicted position.

The SSM is the per-token recurrence (in 256-token stretches, each
recomputed for the backward pass, so it fits on one chip), never the
program's chunked form; attention is computed in 512-row query blocks for
the same reason. A share's layer output is its partial sum, as in the
program. ``dtype=float32`` computes at ``Precision.HIGHEST``;
``dtype=bfloat16`` is the control: parameters, activations and the
recurrent state in bfloat16, the cross entropy reduced in float32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
STRETCH = 256   # tokens of the recurrence between saved states
QBLOCK = 512    # query rows of attention at a time


def _rms(x, scale, eps):
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)
            ).astype(x.dtype)


def _mm(x, w):
    return jnp.matmul(x, w, precision=HIGHEST)


def _rope(x, theta):
    """x (S, H, D): rotate-half RoPE at positions 0..S-1."""
    s, _, d = x.shape
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None]
    cos = jnp.cos(jnp.concatenate([ang, ang], -1))[:, None].astype(x.dtype)
    sin = jnp.sin(jnp.concatenate([ang, ang], -1))[:, None].astype(x.dtype)
    rot = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], -1)
    return x * cos + rot * sin


def _attention(bp, t, m):
    """One sequence: t (S, 2d) -> (S, heads * head_dim), before o_proj."""
    s = t.shape[0]
    h, hd = m["attn_heads"], m["attn_head_dim"]
    q = _rope(_mm(t, bp["wq"]).reshape(s, h, hd), m["rope_theta"])
    k = _rope(_mm(t, bp["wk"]).reshape(s, h, hd), m["rope_theta"])
    v = _mm(t, bp["wv"]).reshape(s, h, hd)
    scale = (hd / 2) ** -0.5

    def rows(qb, first):
        sc = jnp.einsum("qhd,khd->hqk", qb, k, precision=HIGHEST) * scale
        pos = first + jnp.arange(qb.shape[0])
        sc = jnp.where(pos[:, None] >= jnp.arange(s)[None], sc, -jnp.inf)
        p = jax.nn.softmax(sc.astype(jnp.float32), axis=-1).astype(v.dtype)
        return jnp.einsum("hqk,khd->qhd", p, v, precision=HIGHEST)

    if s % QBLOCK or s <= QBLOCK:
        out = rows(q, 0)
    else:
        n = s // QBLOCK
        out = jax.lax.map(lambda a: jax.checkpoint(rows)(*a),
                          (q.reshape(n, QBLOCK, h, hd),
                           jnp.arange(n) * QBLOCK)).reshape(s, h, hd)
    return out.reshape(s, h * hd)


def _block(bp, hp, h, emb, m):
    """The shared block's contribution u_l, for one sequence."""
    eps = m["eps"]
    t = _rms(jnp.concatenate([h, emb], -1), bp["ln_in"]["scale"], eps)
    a = _rms(_mm(_attention(bp, t, m), bp["wo"]), bp["ln_ff"]["scale"], eps)
    gu = _mm(a, bp["gate_up"]) + _mm(_mm(a, hp["adapter_a"]), hp["adapter_b"])
    g, up = gu[:, : m["ffn"]], gu[:, m["ffn"]:]
    g = jax.nn.gelu(g.astype(jnp.float32), approximate=False).astype(a.dtype)
    return _mm(_mm(g * up, bp["down"]), hp["linear"])


def _mamba2(p, x, m):
    """The Mamba2 mixer on one sequence x (S, d)."""
    s = x.shape[0]
    nh, hd, ng, n = (m["mamba_heads"], m["mamba_headdim"], m["mamba_groups"],
                     m["d_state"])
    di, kc = nh * hd, m["d_conv"]
    zxd = _mm(x, p["in_proj"])
    z, xbc, dt = zxd[:, :di], zxd[:, di:-nh], zxd[:, -nh:]
    xpad = jnp.concatenate([jnp.zeros((kc - 1, xbc.shape[1]), xbc.dtype),
                            xbc])
    conv = p["conv_b"] + sum(xpad[i:i + s] * p["conv_w"][i] for i in range(kc))
    xbc = jax.nn.silu(conv)
    xs = xbc[:, :di].reshape(s, nh, hd)
    rep = nh // ng
    bm = jnp.repeat(xbc[:, di:di + ng * n].reshape(s, ng, n), rep, axis=1)
    cm = jnp.repeat(xbc[:, di + ng * n:].reshape(s, ng, n), rep, axis=1)
    dt = jnp.maximum(jax.nn.softplus(dt + p["dt_bias"]),
                     m["dt_min"]).astype(x.dtype)
    a = -jnp.exp(p["a_log"])

    def token(hstate, inp):
        x_t, b_t, c_t, dt_t = inp
        hstate = (jnp.exp(dt_t * a)[:, None, None] * hstate
                  + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :])
        y = jnp.einsum("hpn,hn->hp", hstate, c_t, precision=HIGHEST)
        return hstate, y

    def stretch(hstate, inp):
        return jax.lax.scan(token, hstate, inp)

    seq = (xs, bm, cm, dt)
    if s % STRETCH or s <= STRETCH:
        _, y = stretch(jnp.zeros((nh, hd, n), x.dtype), seq)
    else:
        k = s // STRETCH
        seq = jax.tree_util.tree_map(
            lambda v: v.reshape((k, STRETCH) + v.shape[1:]), seq)
        _, y = jax.lax.scan(jax.checkpoint(stretch),
                            jnp.zeros((nh, hd, n), x.dtype), seq)
    y = y.reshape(s, nh, hd) + p["d_skip"][:, None] * xs
    y = y.reshape(s, di) * jax.nn.silu(z.astype(jnp.float32)).astype(x.dtype)
    yg = y.reshape(s, ng, di // ng).astype(jnp.float32)
    yg = yg * jax.lax.rsqrt(jnp.mean(yg * yg, -1, keepdims=True) + 1e-5)
    y = (yg.reshape(s, di) * p["norm"].astype(jnp.float32)).astype(x.dtype)
    return _mm(y, p["out_proj"])


def _pick(tree, i):
    return jax.tree_util.tree_map(lambda a: a[i], tree)


def logits(params: dict, tokens: jax.Array, *, model: dict,
           dtype=jnp.float32) -> jax.Array:
    """(S, vocab rows) float32 logits of one sequence of token ids (ids
    of the share's rows, which start at rank * rows)."""
    m = model
    p = jax.tree_util.tree_map(lambda x: x.astype(dtype), params)
    g, eps = p["group_0"], m["eps"]
    emb = p["embed"][tokens - m["rank"] * m["vocab"]]
    h = emb
    blocks = max(1, min(m["num_mem_blocks"],
                        sum(t == "hybrid" for t in m["layers"])))
    mamba_layer = jax.checkpoint(lambda lp, h, u: h + _mamba2(
        lp["mixer"], _rms(h + u, lp["ln"]["scale"], eps), m))
    block = jax.checkpoint(lambda bp, hp, h: _block(bp, hp, h, emb, m))
    k = 0
    for layer, kind in enumerate(m["layers"]):
        u = jnp.zeros_like(h)
        if kind == "hybrid":
            u = block(_pick(g["shared_blocks"], k % blocks),
                      _pick(g["hybrid"], k), h)
            k += 1
        h = mamba_layer(_pick(g["mamba"], layer), h, u)
    x = _rms(h, p["final_ln"]["scale"], eps)
    return jnp.einsum("sd,vd->sv", x, p["embed"],
                      precision=HIGHEST).astype(jnp.float32)


def loss(params: dict, tokens: jax.Array, *, model: dict,
         dtype=jnp.float32) -> jax.Array:
    """Mean next-token cross entropy of one node's (batch, seq) tokens."""
    total = 0.0
    for b in range(tokens.shape[0]):
        lg = logits(params, tokens[b], model=model, dtype=dtype)[:-1]
        lse = jax.nn.logsumexp(lg, axis=-1)
        targets = tokens[b, 1:, None] - model["rank"] * model["vocab"]
        picked = jnp.take_along_axis(lg, targets, axis=-1)[:, 0]
        total = total + jnp.sum(lse - picked)
    return total / (tokens.shape[0] * (tokens.shape[1] - 1))


@functools.partial(jax.jit, static_argnames=("model_items", "dtype", "keep"))
def _value_and_grad(params, tokens, *, model_items, dtype, keep):
    value, grads = jax.value_and_grad(loss)(
        params, tokens, model=dict(model_items), dtype=dtype)
    flat = dict(zip(keep, (_at(grads, p) for p in keep)))
    return value, flat


def _at(tree, path: str):
    for k in path.split("/"):
        tree = tree[k]
    return tree


def value_and_grad(params: dict, tokens: jax.Array, *, model: dict,
                   dtype=jnp.float32, keep: tuple[str, ...]):
    """(loss, {path: gradient}) of one node for the leaves in ``keep``,
    jitted per model and dtype (the gradients of the other leaves are
    never formed)."""
    return _value_and_grad(params, tokens,
                           model_items=tuple(sorted(model.items())),
                           dtype=jnp.dtype(dtype).name, keep=tuple(keep))
