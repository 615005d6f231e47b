"""jit'd wrappers: arbitrary-shaped pytree leaves -> padded (R, 128) tiles ->
kernels -> unpadded results. The node-stacked protocol state vmaps over the
leading node axis (pallas_call is vmappable, including interpret mode).

The kernels take ``interpret`` with no default; these wrappers are where it
is decided, by :func:`default_interpret` from the platform: Mosaic on TPU,
the Pallas interpreter elsewhere. :func:`mix_block_fits` decides from N
whether the (N, N)-block mixing kernels fit VMEM at all.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.dpps_perturb import dpps_perturb as _dpps_perturb_kernel
from repro.kernels.l1_clip import clip_scale as _clip_scale_kernel
from repro.kernels.l1_clip import l1_norm as _l1_norm_kernel
from repro.kernels.laplace_noise import LANE, TILE_ROWS
from repro.kernels.laplace_noise import laplace_from_bits as _laplace_kernel
from repro.kernels.pushsum_mix import TILE_D
from repro.kernels.pushsum_mix import pushsum_mix as _pushsum_mix_kernel
from repro.kernels.spmm import spmm as _spmm_kernel

__all__ = [
    "default_interpret",
    "mix_block_fits",
    "laplace_noise_tree",
    "dpps_perturb_tree",
    "dpps_perturb_packed",
    "l1_clip_tree",
    "l1_norm_packed",
    "pushsum_mix",
    "pushsum_mix_sparse",
]

_TILE = TILE_ROWS * LANE  # elements per tile


# The TPU compiler's default scoped VMEM limit (v5e): what one kernel's
# blocks may hold at once.
_SCOPED_VMEM_BYTES = 16 << 20


def default_interpret() -> bool:
    return jax.default_backend() != "tpu"


def mix_block_fits(n_nodes: int) -> bool:
    """Whether ``pushsum_mix`` / ``pushsum_mix_sparse`` fit VMEM at N nodes.

    Both hold an (N, N) f32 weight block (SpMM builds it in VMEM from the
    edge list, with a temporary of the same size) next to double-buffered
    (N, TILE_D) input and output tiles. Above this bound the callers in
    ``repro.core.pushsum`` run the jnp dot / gather path instead.
    """
    block = 4 * n_nodes * n_nodes
    tiles = 4 * 2 * 2 * n_nodes * TILE_D
    return 2 * block + tiles <= _SCOPED_VMEM_BYTES


def _pad_flat(x: jnp.ndarray) -> tuple[jnp.ndarray, int]:
    """Flatten to (R, LANE), padding with zeros to a TILE multiple."""
    flat = x.reshape(-1)
    n = flat.shape[0]
    padded = -(-n // _TILE) * _TILE
    if padded != n:
        flat = jnp.pad(flat, (0, padded - n))
    return flat.reshape(-1, LANE), n


# Padding bits that transform to exactly zero noise: u = 0.5 -> c = 0.
_ZERO_BITS = jnp.uint32(1 << 31)


def _pad_bits(bits_flat: jnp.ndarray, n: int) -> jnp.ndarray:
    padded = -(-n // _TILE) * _TILE
    if padded != n:
        bits_flat = jnp.concatenate(
            [bits_flat, jnp.full((padded - n,), (1 << 31), jnp.uint32)])
    return bits_flat.reshape(-1, LANE)


def laplace_noise_like(key: jax.Array, x: jnp.ndarray, scale,
                       interpret: bool | None = None) -> jnp.ndarray:
    """Kernel-path Laplace noise with the shape of one node's leaf slice."""
    interpret = default_interpret() if interpret is None else interpret
    n = x.size
    bits = jax.random.bits(key, (n,), jnp.uint32)
    tiles = _pad_bits(bits, n)
    noise = _laplace_kernel(tiles, jnp.asarray(scale, jnp.float32),
                            interpret=interpret)
    return noise.reshape(-1)[:n].reshape(x.shape).astype(x.dtype)


def laplace_noise_tree(key: jax.Array, tree, scale, interpret: bool | None = None):
    """Drop-in for privacy.laplace_noise_tree over node-stacked leaves."""
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    keys = jax.random.split(key, len(leaves))
    out = []
    for k, leaf in zip(keys, leaves):
        n_nodes = leaf.shape[0]
        node_keys = jax.random.split(k, n_nodes)
        noise = jax.vmap(
            lambda kk, xx: laplace_noise_like(kk, xx, scale, interpret)
        )(node_keys, leaf)
        out.append(noise)
    return jax.tree_util.tree_unflatten(treedef, out)


def dpps_perturb_flat(s: jnp.ndarray, eps: jnp.ndarray, key: jax.Array,
                      scale, gamma_n, interpret: bool | None = None):
    """One node's fused round op over a single leaf. Returns
    (s_noise like s, eps_l1 scalar, noise_l1 scalar)."""
    interpret = default_interpret() if interpret is None else interpret
    s_t, n = _pad_flat(s)
    eps_t, _ = _pad_flat(eps)
    bits = _pad_bits(jax.random.bits(key, (n,), jnp.uint32), n)
    s_noise, eps_l1, noise_l1 = _dpps_perturb_kernel(
        s_t, eps_t, bits, scale, gamma_n, interpret=interpret)
    s_noise = s_noise.reshape(-1)[:n].reshape(s.shape)
    return s_noise, eps_l1, noise_l1


def dpps_perturb_tree(s_tree, eps_tree, key: jax.Array, scale, gamma_n,
                      interpret: bool | None = None):
    """Fused Alg.-1 lines 3+5 over a node-stacked tree.

    Returns (s_noise tree, eps_l1 (N,), noise_l1 (N,)).
    """
    leaves_s, treedef = jax.tree_util.tree_flatten(s_tree)
    leaves_e = jax.tree_util.tree_leaves(eps_tree)
    n_nodes = leaves_s[0].shape[0]
    keys = jax.random.split(key, len(leaves_s))
    out_leaves, eps_l1, noise_l1 = [], 0.0, 0.0
    for k, ls, le in zip(keys, leaves_s, leaves_e):
        node_keys = jax.random.split(k, n_nodes)
        sn, e1, n1 = jax.vmap(
            lambda kk, ss, ee: dpps_perturb_flat(ss, ee, kk, scale, gamma_n,
                                                 interpret)
        )(node_keys, ls, le)
        out_leaves.append(sn)
        eps_l1 = eps_l1 + e1
        noise_l1 = noise_l1 + n1
    return jax.tree_util.tree_unflatten(treedef, out_leaves), eps_l1, noise_l1


def dpps_perturb_packed(s: jnp.ndarray, eps: jnp.ndarray, key: jax.Array,
                        scale, gamma_n, d_s: int,
                        interpret: bool | None = None):
    """Fused Alg.-1 lines 3+5 over the packed (N, d_pad) buffer.

    One vmapped kernel call for the whole shared state instead of one per
    leaf (``dpps_perturb_tree``). Only the first ``d_s`` lanes are fed to
    the kernel — the layout's padding lanes stay exactly zero (no noise is
    ever drawn for them, so the norms match the un-padded maths) and are
    re-appended to the output. Returns (s_noise (N, d_pad), eps_l1 (N,),
    noise_l1 (N,)).
    """
    interpret = default_interpret() if interpret is None else interpret
    n_nodes, d_pad = s.shape
    s_w, eps_w = s[:, :d_s], eps[:, :d_s]
    node_keys = jax.random.split(key, n_nodes)
    s_noise, eps_l1, noise_l1 = jax.vmap(
        lambda kk, ss, ee: dpps_perturb_flat(ss, ee, kk, scale, gamma_n,
                                             interpret)
    )(node_keys, s_w, eps_w)
    if d_pad != d_s:
        s_noise = jnp.pad(s_noise, ((0, 0), (0, d_pad - d_s)))
    return s_noise, eps_l1, noise_l1


def l1_norm_packed(buf: jnp.ndarray, d_s: int,
                   interpret: bool | None = None) -> jnp.ndarray:
    """Per-node L1 of the packed buffer's ``d_s`` wire lanes -> (N,)."""
    interpret = default_interpret() if interpret is None else interpret

    def node_norm(x):
        tiles, _ = _pad_flat(x)
        return _l1_norm_kernel(tiles, interpret=interpret)

    return jax.vmap(node_norm)(buf[:, :d_s])


def l1_norm_tree(tree, interpret: bool | None = None):
    """Per-node L1 norms of a node-stacked tree via the reduce kernel -> (N,)."""
    interpret = default_interpret() if interpret is None else interpret
    leaves = jax.tree_util.tree_leaves(tree)

    def node_norm(x):
        tiles, _ = _pad_flat(x)
        return _l1_norm_kernel(tiles, interpret=interpret)

    norms = 0.0
    for leaf in leaves:
        norms = norms + jax.vmap(node_norm)(leaf)
    return norms


def l1_clip_tree(tree, clip: float, interpret: bool | None = None):
    """Kernel-path per-node L1 clip (paper Eq. 24) over a node-stacked tree.

    Returns (clipped tree, per-node pre-clip norms (N,))."""
    interpret = default_interpret() if interpret is None else interpret
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    n_nodes = leaves[0].shape[0]

    def node_norm(x):
        tiles, _ = _pad_flat(x)
        return _l1_norm_kernel(tiles, interpret=interpret)

    norms = 0.0
    for leaf in leaves:
        norms = norms + jax.vmap(node_norm)(leaf)
    denom = jnp.maximum(1.0, norms / clip)  # (N,)

    def node_scale(x, d):
        tiles, n = _pad_flat(x)
        out = _clip_scale_kernel(tiles, d, interpret=interpret)
        return out.reshape(-1)[:n].reshape(x.shape).astype(x.dtype)

    clipped = [jax.vmap(node_scale)(leaf, denom) for leaf in leaves]
    return jax.tree_util.tree_unflatten(treedef, clipped), norms


def flash_attention_bshd(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                         window=None, interpret: bool | None = None) -> jnp.ndarray:
    """Model-layout wrapper for kernels.flash_attention.

    q: (B, S, H, D); k, v: (B, S, K, D) (rope already applied). ``window``
    may be a traced scalar (< 0 == global) — it rides through the kernel's
    spec operand, so per-layer windows work inside a layer scan. S is padded
    to the 128 block size (padded keys sit at future positions, so the
    causal mask removes them; padded query rows are sliced off).
    """
    from repro.kernels.flash_attention import BQ, flash_attention

    interpret = default_interpret() if interpret is None else interpret
    b, s, h, d = q.shape
    kh = k.shape[2]
    group = h // kh
    pad = (-s) % BQ
    qt = jnp.moveaxis(q, 2, 1)  # (B, H, S, D)
    kt = jnp.moveaxis(k, 2, 1)
    vt = jnp.moveaxis(v, 2, 1)
    if pad:
        qt = jnp.pad(qt, ((0, 0), (0, 0), (0, pad), (0, 0)))
        kt = jnp.pad(kt, ((0, 0), (0, 0), (0, pad), (0, 0)))
        vt = jnp.pad(vt, ((0, 0), (0, 0), (0, pad), (0, 0)))
    win = jnp.asarray(-1 if window is None else window, jnp.float32)
    out = jax.vmap(
        lambda qq, kk, vv: flash_attention(qq, kk, vv, group=group,
                                           window_dynamic=win,
                                           interpret=interpret)
    )(qt, kt, vt)
    out = jnp.moveaxis(out, 1, 2)[:, :s]
    return out.astype(q.dtype)


def pushsum_mix(w: jnp.ndarray, x: jnp.ndarray, interpret: bool | None = None):
    """Mixing for a (N, ...) node-stacked array via the MXU block kernel."""
    interpret = default_interpret() if interpret is None else interpret
    n = x.shape[0]
    flat = x.reshape(n, -1)
    d = flat.shape[1]
    pad = -(-d // TILE_D) * TILE_D - d
    if pad:
        flat = jnp.pad(flat, ((0, 0), (0, pad)))
    out = _pushsum_mix_kernel(w, flat, interpret=interpret)
    return out[:, :d].reshape(x.shape)


def pushsum_mix_sparse(idx: jnp.ndarray, vals: jnp.ndarray, x: jnp.ndarray,
                       interpret: bool | None = None):
    """Padded-CSR mixing for a (N, ...) node-stacked array (SpMM block)."""
    interpret = default_interpret() if interpret is None else interpret
    n = x.shape[0]
    flat = x.reshape(n, -1)
    d = flat.shape[1]
    pad = -(-d // TILE_D) * TILE_D - d
    if pad:
        flat = jnp.pad(flat, ((0, 0), (0, pad)))
    out = _spmm_kernel(idx, vals, flat, interpret=interpret)
    return out[:, :d].reshape(x.shape)
