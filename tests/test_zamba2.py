"""Zamba2's published hybrid layers (``_ZambaGroupImpl``, ``ssm.mamba2_*``):
the chunked SSD against the per-token recurrence, a tensor-parallel
share's partial sums against the uncut layer, and prefill then decode
through the cache against the full forward."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import Transformer, ssm
from repro.models.transformer import _ZambaGroupImpl

SMOKE = get_config("zamba2-7b").smoke


def _recurrence(x, dt, a, b, c, h0):
    """y_t = C_t . H_t, H_t = exp(dt_t A) H_{t-1} + dt_t x_t B_t^T, token by
    token in numpy float64."""
    x, dt, a, b, c, h = (np.asarray(v, np.float64) for v in (x, dt, a, b, c, h0))
    rep = x.shape[2] // b.shape[2]
    b, c = np.repeat(b, rep, axis=2), np.repeat(c, rep, axis=2)
    ys = []
    for t in range(x.shape[1]):
        h = (np.exp(dt[:, t] * a)[..., None, None] * h
             + (dt[:, t, :, None] * x[:, t])[..., None] * b[:, t, :, None, :])
        ys.append(np.einsum("bhpn,bhn->bhp", h, c[:, t]))
    return np.stack(ys, 1), h


@pytest.mark.parametrize("chunk,seq", [(1, 12), (8, 32), (8, 37), (32, 37),
                                       (32, 64)])
def test_chunked_ssd_matches_the_recurrence(chunk, seq):
    """Whole chunks and a padded last one, from a nonzero state; the state
    it ends in too. 2e-5: float32 against float64."""
    k = jax.random.split(jax.random.PRNGKey(seq + chunk), 6)
    bsz, heads, hp, groups, n = 2, 4, 8, 2, 16
    x = jax.random.normal(k[0], (bsz, seq, heads, hp))
    dt = jax.nn.softplus(jax.random.normal(k[1], (bsz, seq, heads)) - 1.0)
    a = -jnp.exp(jax.random.normal(k[2], (heads,)))
    b = jax.random.normal(k[3], (bsz, seq, groups, n))
    c = jax.random.normal(k[4], (bsz, seq, groups, n))
    h0 = jax.random.normal(k[5], (bsz, heads, hp, n))
    with jax.default_matmul_precision("highest"):
        y, h = ssm.ssd_chunked(x, dt, a, b, c, chunk, h0)
    y_want, h_want = _recurrence(x, dt, a, b, c, h0)
    scale = np.abs(y_want).max()
    np.testing.assert_allclose(np.asarray(y), y_want, atol=2e-5 * scale)
    np.testing.assert_allclose(np.asarray(h), h_want,
                               atol=2e-5 * np.abs(h_want).max())


def _share(whole, impl, rank, size):
    """Share ``rank`` of ``size`` of one layer's whole parameters, cut as
    the deployment divides it (the layout _ZambaGroupImpl documents)."""
    dims = impl.dims
    di, gn, nh = dims.d_inner, dims.n_groups * dims.d_state, dims.n_heads

    def part(v, width, axis=-1):
        w = width // size
        return jax.lax.slice_in_dim(v, rank * w, (rank + 1) * w, axis=axis)

    def sections(v, widths, axis=-1):
        out, off = [], 0
        for w in widths:
            out.append(part(jax.lax.slice_in_dim(v, off, off + w, axis=axis),
                            w, axis))
            off += w
        return jnp.concatenate(out, axis=axis)

    mx = whole["mixer"]
    mixer = {
        "in_proj": sections(mx["in_proj"], (di, di, gn, gn, nh)),
        "conv_w": sections(mx["conv_w"], (di, gn, gn)),
        "conv_b": sections(mx["conv_b"], (di, gn, gn)),
        "dt_bias": part(mx["dt_bias"], nh), "a_log": part(mx["a_log"], nh),
        "d_skip": part(mx["d_skip"], nh), "norm": part(mx["norm"], di),
        "out_proj": part(mx["out_proj"], di, axis=0),
    }
    bp, hp = whole["block"], whole["hybrid"]
    w, ff = bp["wq"].shape[1], bp["down"].shape[0]
    block = dict(bp, wq=part(bp["wq"], w), wk=part(bp["wk"], w),
                 wv=part(bp["wv"], w), wo=part(bp["wo"], w, axis=0),
                 gate_up=sections(bp["gate_up"], (ff, ff)),
                 down=part(bp["down"], ff, axis=0))
    hybrid = dict(hp, adapter_b=sections(hp["adapter_b"], (ff, ff)))
    return {"mixer": mixer, "block": block, "hybrid": hybrid}


def test_shares_add_up_to_the_uncut_layer():
    """A Mamba2 layer, and a shared block's attention and MLP, cut in two:
    the shares' partial outputs add up to the uncut layer's. Norms, the
    linear and adapter A are whole on both shares, so the input each part
    reads is computed alike and counted once."""
    whole_cfg = SMOKE
    impls = {s: _ZambaGroupImpl(whole_cfg.groups[0],
                                dataclasses.replace(whole_cfg, share=(0, s)))
             for s in (1, 2)}
    params = Transformer(whole_cfg).init(jax.random.PRNGKey(0))["group_0"]
    pick = lambda tree, i: jax.tree_util.tree_map(lambda a: a[i], tree)  # noqa: E731
    whole = {"mixer": pick(params["mamba"], 1)["mixer"],
             "block": pick(params["shared_blocks"], 0),
             "hybrid": pick(params["hybrid"], 0)}
    k = jax.random.split(jax.random.PRNGKey(1), 3)
    d = whole_cfg.d_model
    x = jax.random.normal(k[0], (2, 12, d))
    t = jax.random.normal(k[1], (2, 12, 2 * d))
    a = jax.random.normal(k[2], (2, 12, d))
    positions = jnp.broadcast_to(jnp.arange(12)[None], (2, 12))

    def outputs(impl, p):
        y, _ = ssm.mamba2_seq(p["mixer"], x, impl.dims)
        attn, _ = impl._attention(p["block"], t, impl._train_attend(positions))
        return y, attn, impl._mlp(p["block"], p["hybrid"], a)

    with jax.default_matmul_precision("highest"):
        want = outputs(impls[1], whole)
        parts = [outputs(impls[2], _share(whole, impls[1], r, 2))
                 for r in (0, 1)]
    for name, w, p0, p1 in zip(("mamba2", "attention", "mlp"), want, *parts):
        np.testing.assert_allclose(np.asarray(p0 + p1), np.asarray(w),
                                   atol=1e-5 * float(jnp.abs(w).max()),
                                   err_msg=name)


def _check_prefill_then_decode(cfg, prompt, s, seed):
    """Logits of the prompt's prefill and of each later token decoded
    through the cache against the full forward's at the same positions.
    1e-4 of the largest logit: float32 in a different order."""
    model = Transformer(cfg)
    params = model.init(jax.random.PRNGKey(0))
    first, rows = model.vocab
    b = 2
    toks = first + jax.random.randint(jax.random.PRNGKey(seed), (b, s), 0, rows)
    h, _ = jax.jit(model.forward_train)(params, {"tokens": toks})
    full = np.asarray(model._head(params, h))
    logits, cache = jax.jit(model.prefill)(params,
                                           {"tokens": toks[:, :prompt]})
    got = [np.asarray(logits)]
    cache = jax.tree_util.tree_map(
        lambda dst, src: dst.at[tuple(slice(0, n) for n in src.shape)].set(src),
        model.init_cache(b, s), cache)
    decode = jax.jit(model.decode_step)
    for pos in range(prompt, s):
        logits, cache = decode(params, cache, toks[:, pos],
                               jnp.asarray(pos, jnp.int32))
        got.append(np.asarray(logits))
    np.testing.assert_allclose(np.stack(got, 1), full[:, prompt - 1:],
                               atol=1e-4 * np.abs(full).max())


@pytest.mark.parametrize("share", [(0, 1), (1, 2)])
@pytest.mark.parametrize("prompt", [5, 11])
def test_prefill_then_decode_matches_the_forward(share, prompt):
    """Through the SSM and conv states and one KV cache per application;
    the prompts end inside and at the edge of an 8-token SSD chunk."""
    _check_prefill_then_decode(dataclasses.replace(SMOKE, share=share),
                               prompt, 16, 1)


@pytest.mark.parametrize("share", [(0, 1), (1, 2)])
def test_flash_prefill_and_carried_cache_match_the_forward(share):
    """With ``flash_prefill`` and ``decode_cache_in_carry`` set: the prompt
    goes through the flash kernel (q rescaled to Zamba's scale)."""
    _check_prefill_then_decode(
        dataclasses.replace(SMOKE, share=share, flash_prefill=True,
                            decode_cache_in_carry=True), 9, 12, 2)


@pytest.mark.parametrize("what", ["params", "cache"])
def test_published_layout_shards_on_the_model_axis(what):
    """At the published widths every spec has its leaf's rank, every dim
    sharded on ``model`` divides over 16 chips, and the mixer, the
    attention heads, the MLP columns and the caches' heads are sharded."""
    model = Transformer(get_config("zamba2-7b").model)
    if what == "params":
        leaves = jax.eval_shape(model.init, jax.random.PRNGKey(0))
        specs = model.param_pspecs()
        want = ["in_proj", "conv_w", "out_proj", "wq", "wo", "gate_up",
                "down", "adapter_b"]
    else:
        leaves = jax.eval_shape(lambda: model.init_cache(1, 4096))
        specs = model.cache_pspecs()
        want = ["ssm", "conv", "k", "v"]
    flat = jax.tree_util.tree_leaves_with_path(leaves)
    spec_of = dict(jax.tree_util.tree_leaves_with_path(
        specs, is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec)))
    sharded = set()
    for path, leaf in flat:
        spec = spec_of[path]
        assert len(spec) == leaf.ndim, (path, spec, leaf.shape)
        for dim, axis in zip(leaf.shape, spec):
            if axis == "model":
                assert dim % 16 == 0, (path, spec, leaf.shape)
                sharded.add(jax.tree_util.keystr(path[-1:]).strip("[]'"))
    assert set(want) <= sharded, set(want) - sharded
