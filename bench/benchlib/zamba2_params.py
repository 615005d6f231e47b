"""Zamba2 sizes, leaf shapes and seeded weights, for the benchmark alone.

``held(cfg)`` reads a Zamba2 configuration file (the published config.json
keys, a tensor-parallel ``share``) into the sizes one share of each layer
holds. ``leaf_shapes`` gives every per-node leaf by path, in the order the
program's tree flattens; ``params`` makes node-stacked weights from a key
with ``transformers``' Zamba2 initializer: matrices, the conv and the
embedding normal(0, 0.02), biases 0, norm scales 1, dt_bias the inverse
softplus of a log-uniform dt in [time_step_min, time_step_max] floored at
time_step_floor, A_log = log(h) for the held heads' global indices h, D 1.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchlib.weights import nest

INIT_STD = 0.02


def held(cfg: dict) -> dict:
    """Hashable sizes of the share the configuration holds: counts (heads,
    groups, vocabulary rows) as its keys give them, the MLP's columns as
    ``intermediate_size`` over the share's size."""
    rank, size = cfg["share"]["rank"], cfg["share"]["size"]
    return {
        "d_model": cfg["hidden_size"],
        "layers": tuple(cfg["layers_block_type"]),
        "num_mem_blocks": cfg["num_mem_blocks"],
        "adapter_rank": cfg["adapter_rank"],
        "mamba_heads": cfg["n_mamba_heads"],
        "mamba_headdim": cfg["mamba_headdim"],
        "mamba_groups": cfg["mamba_ngroups"],
        "d_state": cfg["mamba_d_state"],
        "d_conv": cfg["mamba_d_conv"],
        "chunk_size": cfg["chunk_size"],
        "attn_heads": cfg["num_attention_heads"],
        "attn_head_dim": cfg["attention_head_dim"],
        "ffn": cfg["intermediate_size"] // size,
        "vocab": cfg["vocab_size"],
        "eps": cfg["rms_norm_eps"],
        "rope_theta": float(cfg["rope_theta"]),
        "dt_min": cfg["time_step_min"],
        "dt_max": cfg["time_step_max"],
        "dt_floor": cfg["time_step_floor"],
        "rank": rank,
    }


def n_hybrid(m: dict) -> int:
    return sum(t == "hybrid" for t in m["layers"])


def n_blocks(m: dict) -> int:
    """Tied blocks the layers apply (a cut can leave one unused)."""
    return min(m["num_mem_blocks"], n_hybrid(m))


def conv_dim(m: dict) -> int:
    return (m["mamba_heads"] * m["mamba_headdim"]
            + 2 * m["mamba_groups"] * m["d_state"])


def leaf_shapes(m: dict) -> dict[str, tuple[int, ...]]:
    """Per-node parameter shapes of the held share, keyed by path."""
    d, r, ff = m["d_model"], m["adapter_rank"], m["ffn"]
    nl, nh, nb = len(m["layers"]), n_hybrid(m), n_blocks(m)
    heads, di = m["mamba_heads"], m["mamba_heads"] * m["mamba_headdim"]
    cd = conv_dim(m)
    w = m["attn_heads"] * m["attn_head_dim"]
    shapes = {
        "embed": (m["vocab"], d),
        "final_ln/scale": (d,),
        "group_0/hybrid/adapter_a": (nh, d, r),
        "group_0/hybrid/adapter_b": (nh, r, 2 * ff),
        "group_0/hybrid/linear": (nh, d, d),
        "group_0/mamba/ln/scale": (nl, d),
        "group_0/mamba/mixer/a_log": (nl, heads),
        "group_0/mamba/mixer/conv_b": (nl, cd),
        "group_0/mamba/mixer/conv_w": (nl, m["d_conv"], cd),
        "group_0/mamba/mixer/d_skip": (nl, heads),
        "group_0/mamba/mixer/dt_bias": (nl, heads),
        "group_0/mamba/mixer/in_proj": (nl, d, di + cd + heads),
        "group_0/mamba/mixer/norm": (nl, di),
        "group_0/mamba/mixer/out_proj": (nl, di, d),
        "group_0/shared_blocks/down": (nb, ff, d),
        "group_0/shared_blocks/gate_up": (nb, d, 2 * ff),
        "group_0/shared_blocks/ln_ff/scale": (nb, d),
        "group_0/shared_blocks/ln_in/scale": (nb, 2 * d),
        "group_0/shared_blocks/wk": (nb, 2 * d, w),
        "group_0/shared_blocks/wo": (nb, w, d),
        "group_0/shared_blocks/wq": (nb, 2 * d, w),
        "group_0/shared_blocks/wv": (nb, 2 * d, w),
    }
    return dict(sorted(shapes.items()))


def _leaf_init(key, path: str, shape, m: dict):
    if path.endswith(("scale", "/norm", "/d_skip")):
        return jnp.ones(shape, jnp.float32)
    if path.endswith("/conv_b"):
        return jnp.zeros(shape, jnp.float32)
    heads = m["mamba_heads"]
    if path.endswith("/a_log"):
        h = m["rank"] * heads + jnp.arange(1, heads + 1, dtype=jnp.float32)
        return jnp.broadcast_to(jnp.log(h), shape)
    if path.endswith("/dt_bias"):
        lo, hi = jnp.log(m["dt_min"]), jnp.log(m["dt_max"])
        dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32) * (hi - lo)
                     + lo)
        dt = jnp.maximum(dt, m["dt_floor"])
        return dt + jnp.log(-jnp.expm1(-dt))
    return INIT_STD * jax.random.normal(key, shape, jnp.float32)


@functools.lru_cache(maxsize=None)
def _params_fn(items: tuple, n_nodes: int):
    m = dict(items)
    shapes = leaf_shapes(m)

    def make(key):
        flat = {p: _leaf_init(jax.random.fold_in(key, i), p, s, m)
                for i, (p, s) in enumerate(shapes.items())}
        # Every node starts from the same parameters (a fresh buffer each).
        return nest({p: jnp.broadcast_to(x[None], (n_nodes,) + x.shape) + 0.0
                     for p, x in flat.items()})

    return jax.jit(make)


def params(m: dict, key: jax.Array, n_nodes: int) -> dict:
    """Node-stacked initial parameters, identical on every node."""
    return _params_fn(tuple(sorted(m.items())), n_nodes)(key)
