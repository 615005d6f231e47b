"""Config-driven decoder transformer covering all assigned arch families.

Key structural decisions:

* Every block group runs as ``lax.scan`` over its layer-stacked params, so
  HLO size is O(#groups); Zamba2 scans each run of Mamba2 layers between
  its hybrid layers (14 scans and 13 hybrid layers at 81 layers).
  Heterogeneous per-layer attention (gemma3 local:global) rides through one
  scan via *traced* per-layer window / rope-theta arrays.
* Training layer bodies are wrapped in ``jax.checkpoint`` (remat) so the
  32k-token prefill and 4k train shapes don't keep every layer's attention
  matrix alive.
* Cross-entropy is computed in vocab-preserving sequence chunks under
  ``jax.checkpoint`` — materializing full (B, S, V) logits for a 262k vocab
  would be hundreds of GB/device.
* Block boundaries carry ``repro.obs`` phase scopes (``model_embed``,
  ``model_attn``, ``model_mlp``, ``model_moe``, ``model_mlstm``,
  ``model_slstm``, ``model_mamba2``, ``model_head``): metadata only, they
  name each block's ops in the compiled program's ``op_name``.
* ``param_pspecs`` returns a PartitionSpec tree aligned with params:
  head/ffn/expert dims shard over the mesh "model" axis; the launcher
  prepends the gossip axes for the node-stacked training state.

Modes:
  forward_train(params, batch)          -> (per-token logits loss path)
  loss_fn(params, batch, key)           -> scalar (next-token CE + MoE aux)
  prefill(params, batch)                -> (logits_last, cache)
  decode_step(params, cache, token, pos)-> (logits, cache)
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models import ssm
from repro.models.attention import (
    cross_attention,
    init_attention,
    init_cross_attention,
)
from repro.models.config import (
    AttnGroup,
    CrossSelfGroup,
    ModelConfig,
    MoEGroup,
    XLSTMGroup,
    ZambaGroup,
)
from repro.models.layers import dense_init, init_rms_norm, mlp_apply, mlp_init, rms_norm, rope, softcap
from repro.models.moe import init_moe, moe_apply
from repro.obs.trace import (
    PHASE_MODEL_ATTN,
    PHASE_MODEL_EMBED,
    PHASE_MODEL_HEAD,
    PHASE_MODEL_MAMBA2,
    PHASE_MODEL_MLP,
    PHASE_MODEL_MLSTM,
    PHASE_MODEL_MOE,
    PHASE_MODEL_SLSTM,
    phase,
)

_NEG_INF = -1e30


# ---------------------------------------------------------------------------
# Attention core (shared by attn / moe / zamba / cross groups)
# ---------------------------------------------------------------------------

def _attn_qkv(params, x, cfg: ModelConfig):
    b, s, _ = x.shape
    q = (x @ params["wq"]).reshape(b, s, cfg.n_heads, cfg.head_dim)
    k = (x @ params["wk"]).reshape(b, s, cfg.n_kv_heads, cfg.head_dim)
    v = (x @ params["wv"]).reshape(b, s, cfg.n_kv_heads, cfg.head_dim)
    return q, k, v


def _attn_train(params, x, positions, cfg: ModelConfig, theta, window,
                use_flash: bool = False):
    """Full-seq causal GQA. window: traced int32 scalar, <0 == global.
    Returns (out, k, v) — k/v feed the prefill cache. ``use_flash`` routes
    the softmax through the Pallas flash kernel (forward-only: prefill)."""
    b, s, _ = x.shape
    group = cfg.n_heads // cfg.n_kv_heads
    q, k, v = _attn_qkv(params, x, cfg)
    q = rope(q, positions, theta)
    k = rope(k, positions, theta)
    if use_flash:
        from repro.kernels import ops as kops

        out = kops.flash_attention_bshd(q, k, v, window=window)
        out = out.reshape(b, s, cfg.n_heads * cfg.head_dim).astype(x.dtype)
        return out @ params["wo"], k, v
    qg = q.reshape(b, s, cfg.n_kv_heads, group, cfg.head_dim)
    scores = jnp.einsum(
        "bskgd,btkd->bkgst", qg.astype(jnp.float32), k.astype(jnp.float32)
    ) / jnp.sqrt(jnp.asarray(cfg.head_dim, jnp.float32))
    qpos = positions[:, None, None, :, None]
    kpos = positions[:, None, None, None, :]
    mask = qpos >= kpos
    w = jnp.asarray(window, jnp.int32)
    mask = mask & jnp.where(w < 0, True, (qpos - kpos) < w)
    probs = jax.nn.softmax(jnp.where(mask, scores, _NEG_INF), axis=-1)
    out = jnp.einsum("bkgst,btkd->bskgd", probs, v.astype(jnp.float32))
    out = out.reshape(b, s, cfg.n_heads * cfg.head_dim).astype(x.dtype)
    return out @ params["wo"], k, v


def _attn_decode_carry(params, x, pos, k_all, v_all, layer_idx,
                       cfg: ModelConfig, theta, window):
    """One-token GQA against layer ``layer_idx`` of a layer-stacked cache,
    updated IN PLACE (token-slot write + layer-slice read — the
    decode_cache_in_carry SPerf path)."""
    b = x.shape[0]
    t = k_all.shape[2]
    group = cfg.n_heads // cfg.n_kv_heads
    posv = jnp.full((b, 1), pos, jnp.int32)
    q, k_new, v_new = _attn_qkv(params, x, cfg)
    q = rope(q, posv, theta)
    k_new = rope(k_new, posv, theta)
    # token-slot write directly into the stacked buffer
    k_all = jax.lax.dynamic_update_slice(
        k_all, k_new[None].astype(k_all.dtype), (layer_idx, 0, pos, 0, 0))
    v_all = jax.lax.dynamic_update_slice(
        v_all, v_new[None].astype(v_all.dtype), (layer_idx, 0, pos, 0, 0))
    # layer-slice read for attention
    k_cache = jax.lax.dynamic_index_in_dim(k_all, layer_idx, 0, keepdims=False)
    v_cache = jax.lax.dynamic_index_in_dim(v_all, layer_idx, 0, keepdims=False)
    qg = q.reshape(b, 1, cfg.n_kv_heads, group, cfg.head_dim)
    scores = jnp.einsum(
        "bskgd,btkd->bkgst", qg.astype(jnp.float32), k_cache.astype(jnp.float32)
    ) / jnp.sqrt(jnp.asarray(cfg.head_dim, jnp.float32))
    slots = jnp.arange(t, dtype=jnp.int32)[None, None, None, None, :]
    mask = slots <= pos
    w = jnp.asarray(window, jnp.int32)
    mask = mask & jnp.where(w < 0, True, (pos - slots) < w)
    probs = jax.nn.softmax(jnp.where(mask, scores, _NEG_INF), axis=-1)
    out = jnp.einsum("bkgst,btkd->bskgd", probs, v_cache.astype(jnp.float32))
    out = out.reshape(b, 1, cfg.n_heads * cfg.head_dim).astype(x.dtype)
    return out @ params["wo"], k_all, v_all


def _attn_decode(params, x, pos, k_cache, v_cache, cfg: ModelConfig, theta, window,
                 ring: bool):
    """One-token GQA against a cache. ``ring``: cache is a sliding ring buffer
    of size == window (static group property)."""
    b = x.shape[0]
    t = k_cache.shape[1]
    group = cfg.n_heads // cfg.n_kv_heads
    posv = jnp.full((b, 1), pos, jnp.int32)
    q, k_new, v_new = _attn_qkv(params, x, cfg)
    q = rope(q, posv, theta)
    k_new = rope(k_new, posv, theta)
    slot = jnp.where(ring, pos % t, pos)
    k_cache = jax.lax.dynamic_update_slice(
        k_cache, k_new.astype(k_cache.dtype), (0, slot, 0, 0))
    v_cache = jax.lax.dynamic_update_slice(
        v_cache, v_new.astype(v_cache.dtype), (0, slot, 0, 0))
    qg = q.reshape(b, 1, cfg.n_kv_heads, group, cfg.head_dim)
    scores = jnp.einsum(
        "bskgd,btkd->bkgst", qg.astype(jnp.float32), k_cache.astype(jnp.float32)
    ) / jnp.sqrt(jnp.asarray(cfg.head_dim, jnp.float32))
    slots = jnp.arange(t, dtype=jnp.int32)[None, None, None, None, :]
    if ring:
        # slot s holds absolute position pos - ((pos - s) mod t); all slots
        # are in-window once pos >= t - 1, else only slots <= pos are valid.
        valid = jnp.where(pos >= t, True, slots <= pos)
        mask = valid
    else:
        mask = slots <= pos
        w = jnp.asarray(window, jnp.int32)
        mask = mask & jnp.where(w < 0, True, (pos - slots) < w)
    probs = jax.nn.softmax(jnp.where(mask, scores, _NEG_INF), axis=-1)
    out = jnp.einsum("bkgst,btkd->bskgd", probs, v_cache.astype(jnp.float32))
    out = out.reshape(b, 1, cfg.n_heads * cfg.head_dim).astype(x.dtype)
    return out @ params["wo"], k_cache, v_cache


def _init_attn_block(key, cfg: ModelConfig, dtype):
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "ln1": init_rms_norm(cfg.d_model, dtype),
        "attn": init_attention(k1, cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
                               cfg.head_dim, dtype),
        "ln2": init_rms_norm(cfg.d_model, dtype),
        "mlp": mlp_init(k2, cfg.d_model, cfg.d_ff, cfg.activation, dtype),
    }


def _attn_block_pspec(cfg: ModelConfig, prefix=()):
    mlp_spec = {"w_up": P(*prefix, None, "model"), "w_down": P(*prefix, "model", None)}
    if cfg.activation in ("silu", "geglu"):
        mlp_spec["w_gate"] = P(*prefix, None, "model")
    return {
        "ln1": {"scale": P(*prefix, None)},
        "attn": {
            "wq": P(*prefix, None, "model"),
            "wk": P(*prefix, None, "model"),
            "wv": P(*prefix, None, "model"),
            "wo": P(*prefix, "model", None),
        },
        "ln2": {"scale": P(*prefix, None)},
        "mlp": mlp_spec,
    }


def _stack_init(key, n, init_one):
    keys = jax.random.split(key, n)
    return jax.vmap(init_one)(keys)


# ---------------------------------------------------------------------------
# Group implementations
# ---------------------------------------------------------------------------

class _GroupImpl:
    """Interface: init / pspec / train / decode / init_cache / cache_pspec."""


class _AttnGroupImpl(_GroupImpl):
    def __init__(self, spec: AttnGroup, cfg: ModelConfig):
        self.spec, self.cfg = spec, cfg
        ws = spec.layer_windows()
        self.windows = jnp.asarray([w if w is not None else -1 for w in ws], jnp.int32)
        self.thetas = jnp.asarray(spec.layer_thetas(cfg.rope_theta), jnp.float32)
        finite = [w for w in ws if w is not None]
        self.uniform_window = finite[0] if (len(finite) == len(ws) and
                                            all(w == finite[0] for w in finite)) else None

    def init(self, key, dtype):
        return _stack_init(key, self.spec.n_layers,
                           lambda k: _init_attn_block(k, self.cfg, dtype))

    def pspec(self):
        return _attn_block_pspec(self.cfg, prefix=(None,))

    def train(self, params, x, positions, enc=None, collect_cache=False,
              use_flash=False):
        cfg = self.cfg

        def body(h, xs):
            lp, window, theta = xs
            with phase(PHASE_MODEL_ATTN):
                a, k, v = _attn_train(lp["attn"],
                                      rms_norm(lp["ln1"], h, cfg.norm_eps),
                                      positions, cfg, theta, window,
                                      use_flash=use_flash)
                h = h + a
            with phase(PHASE_MODEL_MLP):
                h = h + mlp_apply(lp["mlp"],
                                  rms_norm(lp["ln2"], h, cfg.norm_eps),
                                  cfg.activation)
            ys = (k, v) if collect_cache else None
            return h, ys

        x, ys = jax.lax.scan(jax.checkpoint(body), x,
                             (params, self.windows, self.thetas))
        cache = {"k": ys[0], "v": ys[1]} if collect_cache else None
        return x, jnp.zeros((), jnp.float32), cache

    def init_cache(self, batch, capacity, dtype):
        cfg = self.cfg
        t = capacity if self.uniform_window is None else min(capacity, self.uniform_window)
        shape = (self.spec.n_layers, batch, t, cfg.n_kv_heads, cfg.head_dim)
        return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}

    def cache_pspec(self, *, batch_axis=None, seq_axis=None):
        kv = P(None, batch_axis, seq_axis,
               "model" if self.cfg.n_kv_heads % 16 == 0 else None, None)
        return {"k": kv, "v": kv}

    def decode(self, params, x, pos, cache, enc=None):
        cfg = self.cfg
        ring = self.uniform_window is not None

        if cfg.decode_cache_in_carry and not ring:
            idxs = jnp.arange(self.spec.n_layers, dtype=jnp.int32)

            def body(carry, xs):
                h, k_all, v_all = carry
                lp, window, theta, i = xs
                a, k_all, v_all = _attn_decode_carry(
                    lp["attn"], rms_norm(lp["ln1"], h, cfg.norm_eps),
                    pos, k_all, v_all, i, cfg, theta, window)
                h = h + a
                h = h + mlp_apply(lp["mlp"], rms_norm(lp["ln2"], h, cfg.norm_eps),
                                  cfg.activation)
                return (h, k_all, v_all), None

            (x, k, v), _ = jax.lax.scan(
                body, (x, cache["k"], cache["v"]),
                (params, self.windows, self.thetas, idxs))
            return x, {"k": k, "v": v}

        def body(h, xs):
            lp, window, theta, kc, vc = xs
            a, kc, vc = _attn_decode(lp["attn"], rms_norm(lp["ln1"], h, cfg.norm_eps),
                                     pos, kc, vc, cfg, theta, window, ring)
            h = h + a
            h = h + mlp_apply(lp["mlp"], rms_norm(lp["ln2"], h, cfg.norm_eps),
                              cfg.activation)
            return h, (kc, vc)

        x, (k, v) = jax.lax.scan(body, x, (params, self.windows, self.thetas,
                                           cache["k"], cache["v"]))
        return x, {"k": k, "v": v}


class _MoEGroupImpl(_GroupImpl):
    """All-MoE (moe_every=1) or interleaved [moe_every-1 dense + 1 MoE]
    units (llama4-maverick alternation)."""

    def __init__(self, spec: MoEGroup, cfg: ModelConfig):
        self.spec, self.cfg = spec, cfg
        self.n_units = spec.n_units
        self.thetas = jnp.full((self.n_units,), cfg.rope_theta, jnp.float32)
        self.windows = jnp.full((self.n_units,), -1, jnp.int32)
        self._dense_unit = (
            _AttnGroupImpl(AttnGroup(n_layers=spec.moe_every - 1), cfg)
            if spec.moe_every > 1 else None)

    def _init_block(self, key, dtype):
        k1, k2 = jax.random.split(key)
        cfg, spec = self.cfg, self.spec
        return {
            "ln1": init_rms_norm(cfg.d_model, dtype),
            "attn": init_attention(k1, cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
                                   cfg.head_dim, dtype),
            "ln2": init_rms_norm(cfg.d_model, dtype),
            "moe": init_moe(k2, cfg.d_model, cfg.d_ff, spec.n_experts,
                            shared_expert=spec.shared_expert, dtype=dtype),
        }

    def init(self, key, dtype):
        if self._dense_unit is None:
            return _stack_init(key, self.n_units,
                               lambda k: self._init_block(k, dtype))

        def one_unit(k):
            k1, k2 = jax.random.split(k)
            return {"dense": self._dense_unit.init(k1, dtype),
                    "moe": self._init_block(k2, dtype)}

        return _stack_init(key, self.n_units, one_unit)

    def pspec(self):
        cfg = self.cfg
        moe_spec = {
            "router": P(None, None, None),
            "w_gate": P(None, "model", None, None),
            "w_up": P(None, "model", None, None),
            "w_down": P(None, "model", None, None),
        }
        if self.spec.shared_expert:
            moe_spec["shared"] = {
                "w_gate": P(None, None, "model"),
                "w_up": P(None, None, "model"),
                "w_down": P(None, "model", None),
            }
        base = _attn_block_pspec(cfg, prefix=(None,))
        base.pop("mlp")
        base["moe"] = moe_spec
        if self._dense_unit is None:
            return base
        dense_spec = jax.tree_util.tree_map(
            lambda spec: P(*((None,) + tuple(spec))), self._dense_unit.pspec(),
            is_leaf=lambda x: isinstance(x, P))
        return {"dense": dense_spec, "moe": base}

    def _ffn(self, lp, h):
        out, aux = moe_apply(lp["moe"], h, n_experts=self.spec.n_experts,
                             capacity_factor=self.spec.capacity_factor,
                             router_aux_weight=self.spec.router_aux_weight)
        return out, aux

    def train(self, params, x, positions, enc=None, collect_cache=False,
              use_flash=False):
        cfg = self.cfg
        interleaved = self._dense_unit is not None

        def body(carry, xs):
            h, aux = carry
            unit, window, theta = xs
            d_cache = None
            if interleaved:
                h, _, d_cache = self._dense_unit.train(
                    unit["dense"], h, positions, collect_cache=collect_cache,
                    use_flash=use_flash)
                lp = unit["moe"]
            else:
                lp = unit
            with phase(PHASE_MODEL_ATTN):
                a, k, v = _attn_train(lp["attn"],
                                      rms_norm(lp["ln1"], h, cfg.norm_eps),
                                      positions, cfg, theta, window,
                                      use_flash=use_flash)
                h = h + a
            with phase(PHASE_MODEL_MOE):
                f, aux_l = self._ffn(lp, rms_norm(lp["ln2"], h, cfg.norm_eps))
                h = h + f
            ys = ((d_cache, k, v) if interleaved else (k, v)) if collect_cache else None
            return (h, aux + aux_l), ys

        (x, aux), ys = jax.lax.scan(jax.checkpoint(body), (x, jnp.zeros((), jnp.float32)),
                                    (params, self.windows, self.thetas))
        cache = None
        if collect_cache:
            if interleaved:
                cache = {"dense": ys[0], "moe": {"k": ys[1], "v": ys[2]}}
            else:
                cache = {"k": ys[0], "v": ys[1]}
        return x, aux, cache

    def init_cache(self, batch, capacity, dtype):
        cfg = self.cfg
        shape = (self.n_units, batch, capacity, cfg.n_kv_heads, cfg.head_dim)
        moe_kv = {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}
        if self._dense_unit is None:
            return moe_kv
        d = self._dense_unit.init_cache(batch, capacity, dtype)
        d = jax.tree_util.tree_map(
            lambda a: jnp.broadcast_to(a[None], (self.n_units,) + a.shape), d)
        return {"dense": d, "moe": moe_kv}

    def cache_pspec(self, *, batch_axis=None, seq_axis=None):
        kv = P(None, batch_axis, seq_axis,
               "model" if self.cfg.n_kv_heads % 16 == 0 else None, None)
        moe_kv = {"k": kv, "v": kv}
        if self._dense_unit is None:
            return moe_kv
        d = jax.tree_util.tree_map(
            lambda spec: P(*((None,) + tuple(spec))),
            self._dense_unit.cache_pspec(batch_axis=batch_axis, seq_axis=seq_axis),
            is_leaf=lambda x: isinstance(x, P))
        return {"dense": d, "moe": moe_kv}

    def decode(self, params, x, pos, cache, enc=None):
        cfg = self.cfg
        interleaved = self._dense_unit is not None
        moe_cache = cache["moe"] if interleaved else cache

        def body(h, xs):
            if interleaved:
                unit, window, theta, kc, vc, dc = xs
                h, dc = self._dense_unit.decode(unit["dense"], h, pos, dc)
                lp = unit["moe"]
            else:
                unit, window, theta, kc, vc = xs
                lp, dc = unit, None
            a, kc, vc = _attn_decode(lp["attn"], rms_norm(lp["ln1"], h, cfg.norm_eps),
                                     pos, kc, vc, cfg, theta, window, False)
            h = h + a
            f, _ = self._ffn(lp, rms_norm(lp["ln2"], h, cfg.norm_eps))
            h = h + f
            return h, ((kc, vc, dc) if interleaved else (kc, vc))

        if interleaved:
            x, (k, v, d) = jax.lax.scan(
                body, x, (params, self.windows, self.thetas,
                          moe_cache["k"], moe_cache["v"], cache["dense"]))
            return x, {"dense": d, "moe": {"k": k, "v": v}}
        x, (k, v) = jax.lax.scan(body, x, (params, self.windows, self.thetas,
                                           moe_cache["k"], moe_cache["v"]))
        return x, {"k": k, "v": v}


class _XLSTMGroupImpl(_GroupImpl):
    def __init__(self, spec: XLSTMGroup, cfg: ModelConfig):
        self.spec, self.cfg = spec, cfg

    def _init_unit(self, key, dtype):
        cfg, spec = self.cfg, self.spec
        km, ks = jax.random.split(key)
        mk = jax.random.split(km, spec.mlstm_per_unit)

        def one_m(k):
            return {"ln": init_rms_norm(cfg.d_model, dtype),
                    "cell": ssm.init_mlstm(k, cfg.d_model, cfg.n_heads,
                                           spec.proj_factor, dtype)}

        return {
            "mlstm": jax.vmap(one_m)(mk),
            "slstm": {"ln": init_rms_norm(cfg.d_model, dtype),
                      "cell": ssm.init_slstm(ks, cfg.d_model, dtype)},
        }

    def init(self, key, dtype):
        return _stack_init(key, self.spec.n_units,
                           lambda k: self._init_unit(k, dtype))

    def pspec(self):
        m = {
            "w_up": P(None, None, None, "model"),
            "w_q": P(None, None, None, "model"),
            "w_k": P(None, None, None, "model"),
            "w_v": P(None, None, None, "model"),
            "w_if": P(None, None, None, None),
            "b_if": P(None, None, None),
            "w_o": P(None, None, None, "model"),
            "w_down": P(None, None, "model", None),
        }
        s = {"w": P(None, None, None), "r": P(None, None, None), "b": P(None, None)}
        return {
            "mlstm": {"ln": {"scale": P(None, None, None)}, "cell": m},
            "slstm": {"ln": {"scale": P(None, None)}, "cell": s},
        }

    def init_cache(self, batch, capacity, dtype):
        cfg, spec = self.cfg, self.spec
        m = ssm.mlstm_state(batch, cfg.d_model, cfg.n_heads, spec.proj_factor)
        m = jax.tree_util.tree_map(
            lambda x: jnp.broadcast_to(
                x[None, None], (spec.n_units, spec.mlstm_per_unit) + x.shape), m)
        s = ssm.slstm_state(batch, cfg.d_model)
        s = jax.tree_util.tree_map(
            lambda x: jnp.broadcast_to(x[None], (spec.n_units,) + x.shape), s)
        return {"mlstm": m, "slstm": s}

    def cache_pspec(self, *, batch_axis=None, seq_axis=None):
        del seq_axis  # O(1) recurrent state has no sequence dim
        bax = batch_axis
        m = {"C": P(None, None, bax, None, None, None),
             "n": P(None, None, bax, None, None),
             "m": P(None, None, bax, None)}
        s = {k: P(None, bax, None) for k in ("c", "n", "m", "h")}
        return {"mlstm": m, "slstm": s}

    def _unit_train(self, up, x, state):
        cfg = self.cfg

        def m_body(h, xs):
            lp, st = xs
            y, st_new = ssm.mlstm_seq(lp["cell"], rms_norm(lp["ln"], h, cfg.norm_eps),
                                      n_heads=cfg.n_heads, state=st)
            return h + y, st_new

        with phase(PHASE_MODEL_MLSTM):
            x, m_state = jax.lax.scan(jax.checkpoint(m_body), x,
                                      (up["mlstm"], state["mlstm"]))
        sl = up["slstm"]
        with phase(PHASE_MODEL_SLSTM):
            y, s_state = ssm.slstm_seq(sl["cell"],
                                       rms_norm(sl["ln"], x, cfg.norm_eps),
                                       state=state["slstm"])
            x = x + y
        return x, {"mlstm": m_state, "slstm": s_state}

    def train(self, params, x, positions, enc=None, collect_cache=False,
              use_flash=False):
        del use_flash  # attention-free
        b = x.shape[0]
        cache0 = self.init_cache(b, 0, jnp.float32)

        def body(h, xs):
            up, st = xs
            h, st_new = self._unit_train(up, h, st)
            return h, st_new if collect_cache else None

        x, ys = jax.lax.scan(body, x, (params, cache0))
        return x, jnp.zeros((), jnp.float32), (ys if collect_cache else None)

    def decode(self, params, x, pos, cache, enc=None):
        cfg = self.cfg

        def m_body(h, xs):
            lp, st = xs
            y, st_new = ssm.mlstm_step(lp["cell"], rms_norm(lp["ln"], h, cfg.norm_eps),
                                       st, n_heads=cfg.n_heads)
            return h + y, st_new

        def body(h, xs):
            up, st = xs
            h, m_state = jax.lax.scan(m_body, h, (up["mlstm"], st["mlstm"]))
            sl = up["slstm"]
            y, s_state = ssm.slstm_step(sl["cell"], rms_norm(sl["ln"], h, cfg.norm_eps),
                                        st["slstm"])
            return h + y, {"mlstm": m_state, "slstm": s_state}

        x, new_cache = jax.lax.scan(body, x, (params, cache))
        return x, new_cache


ATTN_BLOCK = 512  # query rows per block of the Zamba shared block's attention


def _causal_attn(q, k, v, scale, start=0):
    """softmax(scale q k^T) v over the keys at or before each query's
    position; query i sits at position ``start + i``. q/k/v: (B, S, H, D)."""
    sc = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                    k.astype(jnp.float32)) * scale
    qpos = start + jnp.arange(q.shape[1], dtype=jnp.int32)
    mask = qpos[:, None] >= jnp.arange(k.shape[1], dtype=jnp.int32)[None]
    probs = jax.nn.softmax(jnp.where(mask, sc, _NEG_INF), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v.astype(jnp.float32))


def _causal_attn_blocked(q, k, v, scale):
    """``_causal_attn`` in blocks of ``ATTN_BLOCK`` query rows, each
    rematerialized, so training holds one block's (B, H, block, S) scores
    and not the whole (S, S) matrix."""
    b, s, h, d = q.shape
    if s <= ATTN_BLOCK or s % ATTN_BLOCK:
        return _causal_attn(q, k, v, scale)
    n = s // ATTN_BLOCK
    blocks = jnp.moveaxis(q.reshape(b, n, ATTN_BLOCK, h, d), 1, 0)
    starts = jnp.arange(n, dtype=jnp.int32) * ATTN_BLOCK
    one = jax.checkpoint(lambda qb, st: _causal_attn(qb, k, v, scale, st))
    out = jax.lax.map(lambda xs: one(*xs), (blocks, starts))
    return jnp.moveaxis(out, 0, 1).reshape(b, s, h, d)


class _ZambaGroupImpl(_GroupImpl):
    """Zamba2's hybrid stack as ``transformers``' ``modeling_zamba2`` runs
    it (``Zamba2HybridLayer``, ``Zamba2AttentionDecoderLayer``,
    ``Zamba2MLP``, ``Zamba2MambaDecoderLayer``; the mixer is
    ``ssm.mamba2_seq``).

    Layer l: h <- h + Mamba2_l(norm_l(h + u_l)), with u_l = 0 in a mamba
    layer. In the k-th hybrid layer, with tied block j = k mod
    num_mem_blocks:
        t   = norm_in_j(concat(h, emb))                       (2 d_model)
        a   = norm_ff_j(attn_j(t))     RoPE on q/k, scale (head_dim/2)^-0.5
        u_l = linear_k(down_j(gelu(g) * v)),  [g | v] = gate_up_j(a) + B_k A_k a
    The block has no residual of its own. ``emb`` is the group's input, the
    token embedding (Zamba is its model's only group).

    Parameters: ``mamba`` stacks every layer's mixer and pre-norm,
    ``hybrid`` each application's adapter (A_k, B_k) and ``linear``, and
    ``shared_blocks`` the tied blocks these layers apply: min(num_mem_blocks,
    hybrid layers) of them. Prefill and decode keep one KV cache per
    application and each layer's SSM and conv state.

    A share (rank, size) (``ModelConfig.share``) holds 1/size of each layer:
    whole B/C groups with their heads, conv channels, norm group and
    out_proj rows; attention heads with their o_proj rows; MLP columns of
    gate and up with the matching adapter-B columns and down rows. Norms,
    ``linear`` and adapter A are whole. Each layer's output is the share's
    partial sum, and that is what the next layer reads.

    Departures from the published model: none in the equations. Attention
    runs in blocks of ``ATTN_BLOCK`` query rows, and the mixer's SSD in
    chunks of min(chunk_size, L) (``ssm.ssd_chunked``). With
    ``flash_prefill`` the prefill's attention runs through the flash
    kernel, q rescaled to Zamba's scale.
    """

    def __init__(self, spec: ZambaGroup, cfg: ModelConfig):
        self.spec, self.cfg = spec, cfg
        size = cfg.share[1]
        nh = spec.expand * cfg.d_model // spec.mamba_headdim
        for what, n in (("Mamba2 heads", nh), ("B/C groups", spec.mamba_ngroups),
                        ("attention heads", cfg.n_heads),
                        ("MLP columns", cfg.d_ff)):
            if n % size:
                raise ValueError(f"{n} {what} do not divide into {size} shares")
        if cfg.n_kv_heads != cfg.n_heads:
            raise ValueError("Zamba2 attention has as many kv heads as heads")
        self.dims = ssm.Mamba2Dims(
            n_heads=nh // size, head_dim=spec.mamba_headdim,
            n_groups=spec.mamba_ngroups // size, d_state=spec.d_state,
            d_conv=spec.d_conv, chunk_size=spec.chunk_size,
            dt_min=spec.time_step_min)
        self.heads = cfg.n_heads // size
        self.ff = cfg.d_ff // size
        self.n_layers = spec.total_layers
        self.hybrid = spec.hybrid_layer_ids
        self.n_blocks = min(spec.num_mem_blocks, len(self.hybrid))
        self.scale = (cfg.head_dim / 2) ** -0.5

    # -- parameters and caches ------------------------------------------------
    def init(self, key, dtype):
        cfg, d = self.cfg, self.cfg.d_model
        w = self.heads * cfg.head_dim
        km, kh, kb = jax.random.split(key, 3)

        def mamba(k):
            return {"ln": init_rms_norm(d, dtype),
                    "mixer": ssm.init_mamba2(k, d, self.dims, dtype)}

        def hybrid(k):
            k1, k2, k3 = jax.random.split(k, 3)
            r = self.spec.adapter_rank
            return {"adapter_a": dense_init(k1, (d, r), dtype),
                    "adapter_b": dense_init(k2, (r, 2 * self.ff), dtype),
                    "linear": dense_init(k3, (d, d), dtype)}

        def block(k):
            ks = jax.random.split(k, 6)
            return {"ln_in": init_rms_norm(2 * d, dtype),
                    "wq": dense_init(ks[0], (2 * d, w), dtype),
                    "wk": dense_init(ks[1], (2 * d, w), dtype),
                    "wv": dense_init(ks[2], (2 * d, w), dtype),
                    "wo": dense_init(ks[3], (w, d), dtype),
                    "ln_ff": init_rms_norm(d, dtype),
                    "gate_up": dense_init(ks[4], (d, 2 * self.ff), dtype),
                    "down": dense_init(ks[5], (self.ff, d), dtype)}

        return {"mamba": _stack_init(km, self.n_layers, mamba),
                "hybrid": _stack_init(kh, len(self.hybrid), hybrid),
                "shared_blocks": _stack_init(kb, self.n_blocks, block)}

    def pspec(self):
        """Tensor parallelism over the mesh's ``model`` axis: the mixer's
        in_proj columns, conv channels, heads and out_proj rows; attention
        heads with their o_proj rows; MLP columns with the matching
        adapter-B columns and down rows. Norms of d_model, adapter A and
        ``linear`` are whole."""
        rep2, rep3 = P(None, None), P(None, None, None)
        cols2, cols3 = P(None, "model"), P(None, None, "model")
        rows3 = P(None, "model", None)
        mixer = {"in_proj": cols3, "conv_w": cols3, "out_proj": rows3}
        mixer.update({k: cols2 for k in ("conv_b", "dt_bias", "a_log",
                                         "d_skip", "norm")})
        block = {"ln_in": {"scale": rep2}, "ln_ff": {"scale": rep2},
                 "wq": cols3, "wk": cols3, "wv": cols3, "wo": rows3,
                 "gate_up": cols3, "down": rows3}
        return {"mamba": {"ln": {"scale": rep2}, "mixer": mixer},
                "hybrid": {"adapter_a": rep3, "adapter_b": cols3,
                           "linear": rep3},
                "shared_blocks": block}

    def init_cache(self, batch, capacity, dtype):
        st = ssm.mamba2_state(batch, self.dims, dtype)
        kv = (len(self.hybrid), batch, capacity, self.heads, self.cfg.head_dim)
        return {"mamba": jax.tree_util.tree_map(
                    lambda a: jnp.broadcast_to(a[None], (self.n_layers,) + a.shape),
                    st),
                "attn": {"k": jnp.zeros(kv, dtype), "v": jnp.zeros(kv, dtype)}}

    def cache_pspec(self, *, batch_axis=None, seq_axis=None):
        kv = P(None, batch_axis, seq_axis,
               "model" if self.heads % 16 == 0 else None, None)
        return {"mamba": {"ssm": P(None, batch_axis, "model", None, None),
                          "conv": P(None, batch_axis, None, "model")},
                "attn": {"k": kv, "v": kv}}

    # -- the layers --------------------------------------------------------------
    def _mamba(self, lp, h, u, st, step=False):
        with phase(PHASE_MODEL_MAMBA2):
            x = rms_norm(lp["ln"], h + u, self.cfg.norm_eps)
            fn = ssm.mamba2_step if step else ssm.mamba2_seq
            y, st = fn(lp["mixer"], x, self.dims, st)
            return h + y, st

    def _qkv(self, bp, t, positions):
        b, s, _ = t.shape
        shape = (b, s, self.heads, self.cfg.head_dim)
        theta = self.cfg.rope_theta
        q = rope((t @ bp["wq"]).reshape(shape), positions, theta)
        k = rope((t @ bp["wk"]).reshape(shape), positions, theta)
        return q, k, (t @ bp["wv"]).reshape(shape)

    def _attention(self, bp, t, attend):
        """o_proj(attention(t)); ``attend(bp, t) -> (out, kv)`` runs the
        attention proper (training or decode)."""
        out, kv = attend(bp, t)
        return out.reshape(t.shape[:2] + (-1,)).astype(t.dtype) @ bp["wo"], kv

    def _mlp(self, bp, hp, a):
        """linear_k(down(gelu(g) * up)), [g | up] = a W_gu + (a A_k) B_k."""
        gu = a @ bp["gate_up"] + (a @ hp["adapter_a"]) @ hp["adapter_b"]
        g, up = jnp.split(gu, 2, axis=-1)
        g = jax.nn.gelu(g.astype(jnp.float32), approximate=False)
        return ((g.astype(a.dtype) * up) @ bp["down"]) @ hp["linear"]

    def _block(self, bp, hp, h, emb, attend):
        """The tied block's contribution u to a hybrid layer."""
        eps = self.cfg.norm_eps
        with phase(PHASE_MODEL_ATTN):
            t = rms_norm(bp["ln_in"], jnp.concatenate([h, emb], axis=-1), eps)
            a, kv = self._attention(bp, t, attend)
        with phase(PHASE_MODEL_MLP):
            return self._mlp(bp, hp, rms_norm(bp["ln_ff"], a, eps)), kv

    def _train_attend(self, positions, use_flash=False):
        def attend(bp, t):
            q, k, v = self._qkv(bp, t, positions)
            if use_flash:
                from repro.kernels import ops as kops

                # The kernel scales by head_dim^-0.5; Zamba by (head_dim/2)^-0.5.
                rescale = self.scale * self.cfg.head_dim ** 0.5
                return kops.flash_attention_bshd(q * rescale, k, v), (k, v)
            return _causal_attn_blocked(q, k, v, self.scale), (k, v)

        return attend

    def _layer_params(self, params, k, layer):
        pick = lambda tree, i: jax.tree_util.tree_map(lambda a: a[i], tree)  # noqa: E731
        return (pick(params["shared_blocks"], k % self.n_blocks),
                pick(params["hybrid"], k), pick(params["mamba"], layer))

    def _runs(self):
        """(first, end, hybrid application or None) of each piece in layer
        order: runs of mamba layers scan; a hybrid layer is one piece."""
        out, start = [], 0
        for k, layer in enumerate(self.hybrid):
            if layer > start:
                out.append((start, layer, None))
            out.append((layer, layer + 1, k))
            start = layer + 1
        if start < self.n_layers:
            out.append((start, self.n_layers, None))
        return out

    def train(self, params, x, positions, enc=None, collect_cache=False,
              use_flash=False):
        emb, h = x, x
        states, kvs = [], []
        attend = self._train_attend(positions, use_flash)

        def hybrid_layer(bp, hp, lp, h):
            u, kv = self._block(bp, hp, h, emb, attend)
            h, st = self._mamba(lp, h, u, None)
            return h, ((st, kv) if collect_cache else None)

        def mamba_layer(h, lp):
            h, st = self._mamba(lp, h, 0.0, None)
            return h, (st if collect_cache else None)

        for first, end, k in self._runs():
            if k is None:
                run = jax.tree_util.tree_map(lambda a: a[first:end],
                                             params["mamba"])
                h, st = jax.lax.scan(jax.checkpoint(mamba_layer), h, run)
            else:
                h, out = jax.checkpoint(hybrid_layer)(
                    *self._layer_params(params, k, first), h)
                st = None
                if collect_cache:
                    st = jax.tree_util.tree_map(lambda a: a[None], out[0])
                    kvs.append(out[1])
            states.append(st)
        cache = None
        if collect_cache:
            cache = {"mamba": jax.tree_util.tree_map(
                         lambda *a: jnp.concatenate(a), *states),
                     "attn": {"k": jnp.stack([kv[0] for kv in kvs]),
                              "v": jnp.stack([kv[1] for kv in kvs])}}
        return h, jnp.zeros((), jnp.float32), cache

    def decode(self, params, x, pos, cache, enc=None):
        """One token. Each application writes its token's k/v into its slot
        of the stacked KV cache in place, which is what
        ``decode_cache_in_carry`` asks of the other groups."""
        emb, h = x, x
        b = x.shape[0]
        states = []
        mc = cache["mamba"]
        k_all, v_all = cache["attn"]["k"], cache["attn"]["v"]

        for first, end, k in self._runs():
            run_st = jax.tree_util.tree_map(lambda a: a[first:end], mc)
            if k is None:
                run = jax.tree_util.tree_map(lambda a: a[first:end],
                                             params["mamba"])

                def body(h, xs):
                    lp, st = xs
                    return self._mamba(lp, h, 0.0, st, step=True)

                h, st = jax.lax.scan(body, h, (run, run_st))
            else:
                def attend(bp, t, k=k, k_all=k_all, v_all=v_all):
                    q, kn, vn = self._qkv(bp, t, jnp.full((b, 1), pos, jnp.int32))
                    at = (k, 0, pos, 0, 0)
                    k_all = jax.lax.dynamic_update_slice(
                        k_all, kn[None].astype(k_all.dtype), at)
                    v_all = jax.lax.dynamic_update_slice(
                        v_all, vn[None].astype(v_all.dtype), at)
                    return (_causal_attn(q, k_all[k], v_all[k], self.scale, pos),
                            (k_all, v_all))

                bp, hp, lp = self._layer_params(params, k, first)
                u, (k_all, v_all) = self._block(bp, hp, h, emb, attend)
                h, st = self._mamba(lp, h, u, jax.tree_util.tree_map(
                    lambda a: a[0], run_st), step=True)
                st = jax.tree_util.tree_map(lambda a: a[None], st)
            states.append(st)
        mamba = jax.tree_util.tree_map(lambda *a: jnp.concatenate(a), *states)
        return h, {"mamba": mamba, "attn": {"k": k_all, "v": v_all}}


class _CrossSelfGroupImpl(_GroupImpl):
    """Units of [1 x gated cross-attention + self_per_unit x self-attention]
    consuming stub image embeddings (Llama-3.2-Vision style)."""

    def __init__(self, spec: CrossSelfGroup, cfg: ModelConfig):
        self.spec, self.cfg = spec, cfg
        ag = AttnGroup(n_layers=spec.self_per_unit)
        self._self_unit = _AttnGroupImpl(ag, cfg)

    def init(self, key, dtype):
        k1, k2 = jax.random.split(key)
        cfg = self.cfg

        def one_unit(k):
            ka, kb = jax.random.split(k)
            return {
                "cross_ln": init_rms_norm(cfg.d_model, dtype),
                "cross": init_cross_attention(ka, cfg.d_model, cfg.n_heads,
                                              cfg.n_kv_heads, cfg.head_dim, dtype),
                "self": self._self_unit.init(kb, dtype),
            }

        return _stack_init(key, self.spec.n_units, one_unit)

    def pspec(self):
        self_spec = jax.tree_util.tree_map(
            lambda spec: P(*((None,) + tuple(spec))), self._self_unit.pspec(),
            is_leaf=lambda x: isinstance(x, P))
        return {
            "cross_ln": {"scale": P(None, None)},
            "cross": {
                "wq": P(None, None, "model"),
                "wk": P(None, None, "model"),
                "wv": P(None, None, "model"),
                "wo": P(None, "model", None),
                "gate": P(None, None),
            },
            "self": self_spec,
        }

    def init_cache(self, batch, capacity, dtype):
        c = self._self_unit.init_cache(batch, capacity, dtype)
        return jax.tree_util.tree_map(
            lambda x: jnp.broadcast_to(x[None], (self.spec.n_units,) + x.shape), c)

    def cache_pspec(self, *, batch_axis=None, seq_axis=None):
        inner = self._self_unit.cache_pspec(batch_axis=batch_axis, seq_axis=seq_axis)
        return jax.tree_util.tree_map(
            lambda spec: P(*((None,) + tuple(spec))), inner,
            is_leaf=lambda x: isinstance(x, P))

    def _cross(self, up, h, enc):
        cfg = self.cfg
        with phase(PHASE_MODEL_ATTN):
            y = cross_attention(up["cross"],
                                rms_norm(up["cross_ln"], h, cfg.norm_eps),
                                enc, n_heads=cfg.n_heads,
                                n_kv_heads=cfg.n_kv_heads,
                                head_dim=cfg.head_dim)
            return h + y

    def train(self, params, x, positions, enc=None, collect_cache=False,
              use_flash=False):
        assert enc is not None, "cross_self group needs image embeddings"

        def body(h, up):
            h = self._cross(up, h, enc)
            h, _, c = self._self_unit.train(up["self"], h, positions,
                                            collect_cache=collect_cache,
                                            use_flash=use_flash)
            return h, c

        x, cache = jax.lax.scan(jax.checkpoint(body), x, params)
        return x, jnp.zeros((), jnp.float32), (cache if collect_cache else None)

    def decode(self, params, x, pos, cache, enc=None):
        assert enc is not None

        def body(h, xs):
            up, c = xs
            h = self._cross(up, h, enc)
            h, c_new = self._self_unit.decode(up["self"], h, pos, c)
            return h, c_new

        x, new_cache = jax.lax.scan(body, x, (params, cache))
        return x, new_cache


_GROUP_IMPLS = {
    "attn": _AttnGroupImpl,
    "moe": _MoEGroupImpl,
    "xlstm": _XLSTMGroupImpl,
    "zamba": _ZambaGroupImpl,
    "cross_self": _CrossSelfGroupImpl,
}


# ---------------------------------------------------------------------------
# Full model
# ---------------------------------------------------------------------------

class Transformer:
    """The assembled model: embed -> groups -> final norm -> (tied) LM head."""

    LOSS_CHUNK = 512  # sequence-chunked cross-entropy (vocab stays sharded)

    def __init__(self, cfg: ModelConfig):
        self.cfg = cfg
        self.groups = [_GROUP_IMPLS[g.kind](g, cfg) for g in cfg.groups]

    @property
    def dtype(self):
        return jnp.dtype(self.cfg.param_dtype)

    @property
    def vocab(self) -> tuple[int, int]:
        """(first id, rows) of the vocabulary slice this share holds."""
        rank, size = self.cfg.share
        rows = self.cfg.vocab_size // size
        return rank * rows, rows

    def _rows(self, ids):
        """Token ids -> rows of the held vocabulary slice."""
        first = self.vocab[0]
        return ids - first if first else ids

    # -- parameters -----------------------------------------------------------
    def init(self, key: jax.Array) -> dict:
        cfg = self.cfg
        keys = jax.random.split(key, len(self.groups) + 2)
        rows = self.vocab[1]
        params: dict[str, Any] = {
            "embed": dense_init(keys[0], (rows, cfg.d_model), self.dtype),
            "final_ln": init_rms_norm(cfg.d_model, self.dtype),
        }
        if not cfg.tie_embedding:
            params["lm_head"] = dense_init(keys[1], (cfg.d_model, rows),
                                           self.dtype)
        for i, g in enumerate(self.groups):
            params[f"group_{i}"] = g.init(keys[i + 2], self.dtype)
        return params

    def param_pspecs(self) -> dict:
        cfg = self.cfg
        specs: dict[str, Any] = {
            "embed": P("model", None),
            "final_ln": {"scale": P(None)},
        }
        if not cfg.tie_embedding:
            specs["lm_head"] = P(None, "model")
        for i, g in enumerate(self.groups):
            specs[f"group_{i}"] = g.pspec()
        return specs

    # -- forward --------------------------------------------------------------
    def _embed_inputs(self, params, batch):
        cfg = self.cfg
        with phase(PHASE_MODEL_EMBED):
            if cfg.input_mode == "embeddings":
                x = batch["embeds"].astype(self.dtype)
            else:
                x = params["embed"][self._rows(batch["tokens"])]
            if cfg.embed_scale:
                x = x * jnp.asarray(jnp.sqrt(cfg.d_model), x.dtype)
            return x

    def _labels(self, batch):
        return batch["labels"] if "labels" in batch else batch["tokens"]

    def _backbone(self, params, x, positions, enc, collect_cache=False,
                  use_flash=False):
        aux = jnp.zeros((), jnp.float32)
        caches = {}
        for i, g in enumerate(self.groups):
            x, a, c = g.train(params[f"group_{i}"], x, positions, enc=enc,
                              collect_cache=collect_cache, use_flash=use_flash)
            aux = aux + a
            if collect_cache:
                caches[f"group_{i}"] = c
        with phase(PHASE_MODEL_HEAD):
            x = rms_norm(params["final_ln"], x, self.cfg.norm_eps)
        return x, aux, caches

    def _head(self, params, x):
        cfg = self.cfg
        if cfg.tie_embedding:
            logits = x @ params["embed"].T
        else:
            logits = x @ params["lm_head"]
        return softcap(logits.astype(jnp.float32), cfg.logit_softcap)

    def forward_train(self, params, batch):
        """Returns (final hidden states (B,S,d), aux loss). Logits are
        produced chunked inside loss_fn to bound memory."""
        x = self._embed_inputs(params, batch)
        b, s = x.shape[:2]
        positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))
        enc = batch.get("image_embeds") if isinstance(batch, dict) else None
        h, aux, _ = self._backbone(params, x, positions, enc)
        return h, aux

    def loss_fn(self, params, batch, key=None) -> jnp.ndarray:
        """Mean next-token cross entropy (+ MoE aux), seq-chunked over vocab."""
        cfg = self.cfg
        h, aux = self.forward_train(params, batch)
        with phase(PHASE_MODEL_HEAD):
            labels = self._labels(batch)
            # predict token t+1 from hidden t
            h = h[:, :-1]
            targets = self._rows(labels[:, 1:])
            b, sm1, d = h.shape
            chunk = min(self.LOSS_CHUNK, sm1)
            n_chunks = sm1 // chunk
            rem = sm1 - n_chunks * chunk

            head = params["embed"] if cfg.tie_embedding else None

            def chunk_loss(h_c, t_c):
                logits = self._head(params, h_c)  # (B, c, V) f32
                lse = jax.nn.logsumexp(logits, axis=-1)
                picked = jnp.take_along_axis(logits, t_c[..., None], axis=-1)[..., 0]
                return jnp.sum(lse - picked)

            chunk_loss = jax.checkpoint(chunk_loss)

            total = jnp.zeros((), jnp.float32)
            if n_chunks > 0:
                h_chunks = h[:, : n_chunks * chunk].reshape(b, n_chunks, chunk, d)
                t_chunks = targets[:, : n_chunks * chunk].reshape(b, n_chunks, chunk)

                def body(acc, xs):
                    h_c, t_c = xs
                    return acc + chunk_loss(h_c, t_c), None

                total, _ = jax.lax.scan(
                    body, total,
                    (jnp.moveaxis(h_chunks, 1, 0), jnp.moveaxis(t_chunks, 1, 0)))
            if rem:
                total = total + chunk_loss(h[:, n_chunks * chunk:],
                                           targets[:, n_chunks * chunk:])
            return total / (b * sm1) + aux

    # -- serving ----------------------------------------------------------------
    def init_cache(self, batch: int, capacity: int, dtype=None) -> dict:
        dtype = dtype or self.dtype
        return {f"group_{i}": g.init_cache(batch, capacity, dtype)
                for i, g in enumerate(self.groups)}

    def cache_pspecs(self, *, batch_axis="data", seq_axis=None) -> dict:
        return {f"group_{i}": g.cache_pspec(batch_axis=batch_axis, seq_axis=seq_axis)
                for i, g in enumerate(self.groups)}

    def prefill(self, params, batch):
        """Forward over the prompt, returning (last-token logits, cache)."""
        x = self._embed_inputs(params, batch)
        b, s = x.shape[:2]
        positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))
        enc = batch.get("image_embeds") if isinstance(batch, dict) else None
        h, _, caches = self._backbone(params, x, positions, enc,
                                      collect_cache=True,
                                      use_flash=self.cfg.flash_prefill)
        with phase(PHASE_MODEL_HEAD):
            logits = self._head(params, h[:, -1:])
        return logits[:, 0], caches

    def decode_step(self, params, cache, token, pos, enc=None):
        """One token for the whole batch. token: (B,) int32 (or (B, d) embeds
        for embedding-input models); pos: scalar int32."""
        cfg = self.cfg
        if cfg.input_mode == "embeddings":
            x = token[:, None, :].astype(self.dtype)
        else:
            x = params["embed"][self._rows(token)][:, None, :]
        if cfg.embed_scale:
            x = x * jnp.asarray(jnp.sqrt(cfg.d_model), x.dtype)
        new_cache = {}
        for i, g in enumerate(self.groups):
            x, c = g.decode(params[f"group_{i}"], x, pos, cache[f"group_{i}"], enc=enc)
            new_cache[f"group_{i}"] = c
        with phase(PHASE_MODEL_HEAD):
            x = rms_norm(params["final_ln"], x, cfg.norm_eps)
            logits = self._head(params, x)
        return logits[:, 0], new_cache
