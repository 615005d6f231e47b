"""Compile rehearsals: every main-path Pallas kernel, compiled by the TPU
compiler (``interpret=False``) for a described, unattached v5e chip.

Interpret mode cannot see what Mosaic refuses — unaligned blocks, casts it
has no lowering for, VMEM overruns — so each kernel is compiled here at the
widths the protocol runs: the packed paper-MLP buffer (N=16, d_s=7850 ->
d_pad 7936) through the ``ops`` wrappers, and the sparse schedule at
N=4096, where the SpMM block no longer fits VMEM and the mix hands off to
the jnp gather path. Nothing runs; the tests read the compiled HLO.

The topology is described inside a module fixture (never at import time):
only one process may load the TPU library, and only the worker given this
file should.
"""
import inspect

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.pushsum import (
    PushSumState,
    _mix_dense,
    gossip_packed,
    sparse_mix,
)
from repro.kernels import ops
from repro.kernels.dpps_perturb import dpps_perturb
from repro.kernels.flash_attention import flash_attention
from repro.kernels.l1_clip import clip_scale, l1_norm
from repro.kernels.laplace_noise import LANE, TILE_ROWS, laplace_from_bits
from repro.kernels.pushsum_mix import TILE_D, pushsum_mix
from repro.kernels.spmm import spmm

N, D_S, D_PAD = 16, 7850, 7936      # paper-MLP shared layer, packed
N_SPARSE, K_SPARSE = 4096, 24       # BENCH_sparse's largest ER graph
# the shared state as whole (TILE_ROWS, 128) tiles, as _pad_flat lays it out
ROWS = -(-N * D_S // (TILE_ROWS * LANE)) * TILE_ROWS

KERNEL = "tpu_custom_call"


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def _no_persistent_cache():
    # A compile for a described chip is written to the persistent cache but
    # cannot be read back without one; keep these compiles out of it.
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _hlo(fn, *shapes) -> str:
    return jax.jit(fn).lower(*shapes).compile().as_text()


def _spec(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def test_dpps_perturb_packed_compiles(one_chip):
    buf = _spec(one_chip, (N, D_PAD))
    key = _spec(one_chip, (2,), jnp.uint32)
    txt = _hlo(lambda s, e, k: ops.dpps_perturb_packed(
        s, e, k, 0.5, 1e-3, D_S, interpret=False), buf, buf, key)
    assert KERNEL in txt


def test_dpps_perturb_kernel_compiles(one_chip):
    tile = _spec(one_chip, (ROWS, LANE))
    bits = _spec(one_chip, (ROWS, LANE), jnp.uint32)
    txt = _hlo(lambda s, e, b: dpps_perturb(s, e, b, 0.5, 1e-3,
                                            interpret=False),
               tile, tile, bits)
    assert KERNEL in txt


def test_laplace_from_bits_compiles(one_chip):
    bits = _spec(one_chip, (ROWS, LANE), jnp.uint32)
    assert KERNEL in _hlo(lambda b: laplace_from_bits(b, 0.5, interpret=False),
                          bits)


def test_laplace_noise_tree_compiles(one_chip):
    key = _spec(one_chip, (2,), jnp.uint32)
    leaf = _spec(one_chip, (N, D_S))
    assert KERNEL in _hlo(lambda k, x: ops.laplace_noise_tree(
        k, [x], 0.5, interpret=False), key, leaf)


def test_l1_norm_packed_compiles(one_chip):
    buf = _spec(one_chip, (N, D_PAD))
    assert KERNEL in _hlo(lambda b: ops.l1_norm_packed(b, D_S,
                                                       interpret=False), buf)


def test_l1_norm_kernel_compiles(one_chip):
    tile = _spec(one_chip, (ROWS, LANE))
    assert KERNEL in _hlo(lambda x: l1_norm(x, interpret=False), tile)


def test_clip_scale_compiles(one_chip):
    tile = _spec(one_chip, (ROWS, LANE))
    assert KERNEL in _hlo(lambda x: clip_scale(x, 3.0, interpret=False),
                          tile)


def test_l1_clip_tree_compiles(one_chip):
    # per-node denominators: the clip kernel's SMEM scalar under vmap
    leaf = _spec(one_chip, (N, D_S))
    assert KERNEL in _hlo(lambda x: ops.l1_clip_tree([x], 5.0,
                                                     interpret=False), leaf)


def test_pushsum_mix_compiles(one_chip):
    w = _spec(one_chip, (N, N))
    buf = _spec(one_chip, (N, D_PAD))
    assert KERNEL in _hlo(lambda w_, x: ops.pushsum_mix(w_, x,
                                                        interpret=False),
                          w, buf)
    assert KERNEL in _hlo(lambda w_, x: pushsum_mix(w_, x, interpret=False),
                          w, _spec(one_chip, (N, 16 * TILE_D)))


def test_spmm_compiles(one_chip):
    idx = _spec(one_chip, (N, 3), jnp.int32)
    vals = _spec(one_chip, (N, 3))
    buf = _spec(one_chip, (N, D_PAD))
    assert KERNEL in _hlo(lambda i, v, x: ops.pushsum_mix_sparse(
        i, v, x, interpret=False), idx, vals, buf)
    assert KERNEL in _hlo(lambda i, v, x: spmm(i, v, x, interpret=False),
                          idx, vals, _spec(one_chip, (N, 16 * TILE_D)))


@pytest.mark.parametrize("n_nodes,kernel", [(N, True), (N_SPARSE, False)])
def test_sparse_gossip_routes_by_vmem(one_chip, n_nodes, kernel,
                                      monkeypatch):
    """The packed sparse mix takes the SpMM kernel while its (N, N) block
    fits VMEM, and the jnp gather path above that."""
    monkeypatch.setattr(ops, "default_interpret", lambda: False)
    assert ops.mix_block_fits(n_nodes) is kernel
    idx = _spec(one_chip, (n_nodes, K_SPARSE), jnp.int32)
    vals = _spec(one_chip, (n_nodes, K_SPARSE))
    s = _spec(one_chip, (n_nodes, LANE))
    a = _spec(one_chip, (n_nodes,))

    def mix(s_, a_, i, v):
        out = gossip_packed(PushSumState(s=s_, a=a_), sparse_idx=i,
                            sparse_vals=v, use_kernels=True)
        return out.s, out.a

    txt = _hlo(mix, s, a, idx, vals)
    assert (KERNEL in txt) is kernel
    if not kernel:
        assert "gather" in txt


@pytest.mark.parametrize("window", [None, 1024])
def test_flash_attention_compiles(one_chip, window):
    # llama3.2-1b prefill heads: 32 query / 8 kv heads of width 64
    q = _spec(one_chip, (1, 2048, 32, 64), jnp.bfloat16)
    kv = _spec(one_chip, (1, 2048, 8, 64), jnp.bfloat16)
    assert KERNEL in _hlo(lambda q_, k_, v_: ops.flash_attention_bshd(
        q_, k_, v_, window=window, interpret=False), q, kv, kv)
    qh = _spec(one_chip, (32, 2048, 64), jnp.bfloat16)
    kh = _spec(one_chip, (8, 2048, 64), jnp.bfloat16)
    assert KERNEL in _hlo(lambda q_, k_, v_: flash_attention(
        q_, k_, v_, group=4, window=window, interpret=False), qh, kh, kh)


@pytest.mark.parametrize("entry", [dpps_perturb, laplace_from_bits, l1_norm,
                                   clip_scale, pushsum_mix, spmm,
                                   flash_attention])
def test_kernel_entry_points_have_no_interpret_default(entry):
    """Only ops.default_interpret decides interpret mode, from the platform."""
    param = inspect.signature(entry).parameters["interpret"]
    assert param.default is inspect.Parameter.empty


@pytest.mark.parametrize("mix", ["dense", "sparse", "kernel_dense",
                                 "kernel_sparse"])
def test_mixes_contract_at_f32_precision(mix):
    """An f32 dot on TPU defaults to one bf16 pass (max error 5.6e-3 on a
    16-node mix, against 1.5e-7 at HIGHEST, on a v5e chip); every mixing
    contraction asks for full precision."""
    w = jnp.ones((4, 4))
    idx, vals = jnp.zeros((4, 2), jnp.int32), jnp.ones((4, 2))
    x = jnp.ones((4, TILE_D))
    fn = {"dense": lambda: _mix_dense(w, x),
          "sparse": lambda: sparse_mix(idx, vals, x),
          "kernel_dense": lambda: pushsum_mix(w, x, interpret=False),
          "kernel_sparse": lambda: spmm(idx, vals, x, interpret=False)}[mix]
    jaxpr = str(jax.make_jaxpr(fn)())
    assert "dot_general" in jaxpr
    assert "precision=None" not in jaxpr
    assert "Precision.HIGHEST" in jaxpr


def test_mix_block_bound_tracks_vmem():
    assert ops.mix_block_fits(16) and ops.mix_block_fits(1024)
    assert not ops.mix_block_fits(2048)
    assert not ops.mix_block_fits(N_SPARSE)
