"""Config-driven decoder transformer covering all assigned arch families.

Key structural decisions (see DESIGN.md):

* Every block group runs as ``lax.scan`` over its layer-stacked params, so
  HLO size is O(#groups) — 81-layer Zamba2 compiles as ~3 scans.
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
    MambaGroup,
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


class _MambaGroupImpl(_GroupImpl):
    def __init__(self, spec: MambaGroup, cfg: ModelConfig, n_layers=None):
        self.spec, self.cfg = spec, cfg
        self.n_layers = n_layers if n_layers is not None else spec.n_layers

    def _init_block(self, key, dtype):
        cfg, spec = self.cfg, self.spec
        return {"ln": init_rms_norm(cfg.d_model, dtype),
                "cell": ssm.init_mamba2(key, cfg.d_model, spec.d_state,
                                        spec.expand, 64, dtype)}

    def init(self, key, dtype):
        return _stack_init(key, self.n_layers, lambda k: self._init_block(k, dtype))

    def pspec(self):
        cell = {
            "w_in": P(None, None, "model"),
            "w_b": P(None, None, None),
            "w_c": P(None, None, None),
            "w_dt": P(None, None, None),
            "b_dt": P(None, None),
            "a_log": P(None, None),
            "d_skip": P(None, None),
            "w_out": P(None, "model", None),
        }
        return {"ln": {"scale": P(None, None)}, "cell": cell}

    def init_cache(self, batch, capacity, dtype):
        cfg, spec = self.cfg, self.spec
        st = ssm.mamba2_state(batch, cfg.d_model, spec.d_state, spec.expand, 64)
        return jax.tree_util.tree_map(
            lambda x: jnp.broadcast_to(x[None], (self.n_layers,) + x.shape), st)

    def cache_pspec(self, *, batch_axis=None, seq_axis=None):
        del seq_axis  # O(1) recurrent state has no sequence dim
        return {"h": P(None, batch_axis, "model", None, None)}

    def train(self, params, x, positions, enc=None, collect_cache=False,
              use_flash=False):
        del use_flash  # attention-free
        cfg = self.cfg
        b = x.shape[0]
        cache0 = self.init_cache(b, 0, jnp.float32)

        def body(h, xs):
            lp, st = xs
            with phase(PHASE_MODEL_MAMBA2):
                y, st_new = ssm.mamba2_seq(lp["cell"],
                                           rms_norm(lp["ln"], h, cfg.norm_eps),
                                           head_dim=64, state=st)
                h = h + y
            return h, (st_new if collect_cache else None)

        x, ys = jax.lax.scan(jax.checkpoint(body), x, (params, cache0))
        return x, jnp.zeros((), jnp.float32), (ys if collect_cache else None)

    def decode(self, params, x, pos, cache, enc=None):
        cfg = self.cfg

        def body(h, xs):
            lp, st = xs
            y, st_new = ssm.mamba2_step(lp["cell"], rms_norm(lp["ln"], h, cfg.norm_eps),
                                        st, head_dim=64)
            return h + y, st_new

        x, new_cache = jax.lax.scan(body, x, (params, cache))
        return x, new_cache


class _ZambaGroupImpl(_GroupImpl):
    """Units of [mamba_per_unit x Mamba2 + 1 x shared-weight attention].

    The attention block's parameters are shared across units (Zamba2's
    parameter-efficiency trick); each unit application keeps its own KV
    cache. Trailing Mamba2 layers run after the units.
    """

    def __init__(self, spec: ZambaGroup, cfg: ModelConfig):
        self.spec, self.cfg = spec, cfg
        mg = MambaGroup(n_layers=spec.mamba_per_unit, d_state=spec.d_state,
                        expand=spec.expand)
        self._mamba_unit = _MambaGroupImpl(mg, cfg, n_layers=spec.mamba_per_unit)
        self._trailing = (_MambaGroupImpl(
            MambaGroup(n_layers=spec.trailing_mamba, d_state=spec.d_state,
                       expand=spec.expand), cfg, n_layers=spec.trailing_mamba)
            if spec.trailing_mamba else None)

    def init(self, key, dtype):
        k1, k2, k3 = jax.random.split(key, 3)
        params = {
            "units_mamba": _stack_init(
                k1, self.spec.n_units, lambda k: self._mamba_unit.init(k, dtype)),
            "shared_attn": _init_attn_block(k2, self.cfg, dtype),
        }
        if self._trailing is not None:
            params["trailing"] = self._trailing.init(k3, dtype)
        return params

    def pspec(self):
        unit_m = jax.tree_util.tree_map(
            lambda spec: P(*((None,) + tuple(spec))), self._mamba_unit.pspec(),
            is_leaf=lambda x: isinstance(x, P))
        out = {
            "units_mamba": unit_m,
            "shared_attn": _attn_block_pspec(self.cfg, prefix=()),
        }
        if self._trailing is not None:
            out["trailing"] = self._trailing.pspec()
        return out

    def init_cache(self, batch, capacity, dtype):
        cfg, spec = self.cfg, self.spec
        m = self._mamba_unit.init_cache(batch, capacity, dtype)
        m = jax.tree_util.tree_map(
            lambda x: jnp.broadcast_to(x[None], (spec.n_units,) + x.shape), m)
        kv_shape = (spec.n_units, batch, capacity, cfg.n_kv_heads, cfg.head_dim)
        cache = {"mamba": m,
                 "attn": {"k": jnp.zeros(kv_shape, dtype),
                          "v": jnp.zeros(kv_shape, dtype)}}
        if self._trailing is not None:
            cache["trailing"] = self._trailing.init_cache(batch, capacity, dtype)
        return cache

    def cache_pspec(self, *, batch_axis=None, seq_axis=None):
        m = jax.tree_util.tree_map(
            lambda spec: P(*((None,) + tuple(spec))),
            self._mamba_unit.cache_pspec(batch_axis=batch_axis),
            is_leaf=lambda x: isinstance(x, P))
        kv = P(None, batch_axis, seq_axis,
               "model" if self.cfg.n_kv_heads % 16 == 0 else None, None)
        out = {"mamba": m, "attn": {"k": kv, "v": kv}}
        if self._trailing is not None:
            out["trailing"] = self._trailing.cache_pspec(batch_axis=batch_axis)
        return out

    def train(self, params, x, positions, enc=None, collect_cache=False,
              use_flash=False):
        cfg = self.cfg
        shared = params["shared_attn"]

        def body(h, up):
            h, _, m_cache = self._mamba_unit.train(up, h, positions,
                                                   collect_cache=collect_cache)
            with phase(PHASE_MODEL_ATTN):
                a, k, v = _attn_train(shared["attn"],
                                      rms_norm(shared["ln1"], h, cfg.norm_eps),
                                      positions, cfg,
                                      jnp.asarray(cfg.rope_theta, jnp.float32),
                                      jnp.asarray(-1, jnp.int32),
                                      use_flash=use_flash)
                h = h + a
            with phase(PHASE_MODEL_MLP):
                h = h + mlp_apply(shared["mlp"],
                                  rms_norm(shared["ln2"], h, cfg.norm_eps),
                                  cfg.activation)
            ys = (m_cache, k, v) if collect_cache else None
            return h, ys

        x, ys = jax.lax.scan(jax.checkpoint(body), x, params["units_mamba"])
        cache = None
        if collect_cache:
            cache = {"mamba": ys[0], "attn": {"k": ys[1], "v": ys[2]}}
        aux = jnp.zeros((), jnp.float32)
        if self._trailing is not None:
            x, _, tr_cache = self._trailing.train(params["trailing"], x, positions,
                                                  collect_cache=collect_cache)
            if collect_cache:
                cache["trailing"] = tr_cache
        return x, aux, cache

    def decode(self, params, x, pos, cache, enc=None):
        cfg = self.cfg
        shared = params["shared_attn"]

        if cfg.decode_cache_in_carry:
            idxs = jnp.arange(self.spec.n_units, dtype=jnp.int32)

            def body_c(carry, xs):
                h, k_all, v_all = carry
                up, m_st, i = xs
                h, m_new = self._mamba_unit.decode(up, h, pos, m_st)
                a, k_all, v_all = _attn_decode_carry(
                    shared["attn"], rms_norm(shared["ln1"], h, cfg.norm_eps),
                    pos, k_all, v_all, i, cfg,
                    jnp.asarray(cfg.rope_theta, jnp.float32),
                    jnp.asarray(-1, jnp.int32))
                h = h + a
                h = h + mlp_apply(shared["mlp"],
                                  rms_norm(shared["ln2"], h, cfg.norm_eps),
                                  cfg.activation)
                return (h, k_all, v_all), m_new

            (x, k, v), m_new = jax.lax.scan(
                body_c, (x, cache["attn"]["k"], cache["attn"]["v"]),
                (params["units_mamba"], cache["mamba"], idxs))
            new_cache = {"mamba": m_new, "attn": {"k": k, "v": v}}
            if self._trailing is not None:
                x, tr = self._trailing.decode(params["trailing"], x, pos,
                                              cache["trailing"])
                new_cache["trailing"] = tr
            return x, new_cache

        def body(h, xs):
            up, m_st, kc, vc = xs
            h, m_new = self._mamba_unit.decode(up, h, pos, m_st)
            a, kc, vc = _attn_decode(shared["attn"],
                                     rms_norm(shared["ln1"], h, cfg.norm_eps),
                                     pos, kc, vc, cfg,
                                     jnp.asarray(cfg.rope_theta, jnp.float32),
                                     jnp.asarray(-1, jnp.int32), False)
            h = h + a
            h = h + mlp_apply(shared["mlp"], rms_norm(shared["ln2"], h, cfg.norm_eps),
                              cfg.activation)
            return h, (m_new, kc, vc)

        x, (m_new, k, v) = jax.lax.scan(
            body, x, (params["units_mamba"], cache["mamba"],
                      cache["attn"]["k"], cache["attn"]["v"]))
        new_cache = {"mamba": m_new, "attn": {"k": k, "v": v}}
        if self._trailing is not None:
            x, tr = self._trailing.decode(params["trailing"], x, pos, cache["trailing"])
            new_cache["trailing"] = tr
        return x, new_cache


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
    "mamba": _MambaGroupImpl,
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

    # -- parameters -----------------------------------------------------------
    def init(self, key: jax.Array) -> dict:
        cfg = self.cfg
        keys = jax.random.split(key, len(self.groups) + 2)
        params: dict[str, Any] = {
            "embed": dense_init(keys[0], (cfg.vocab_size, cfg.d_model), self.dtype),
            "final_ln": init_rms_norm(cfg.d_model, self.dtype),
        }
        if not cfg.tie_embedding:
            params["lm_head"] = dense_init(keys[1], (cfg.d_model, cfg.vocab_size),
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
                x = params["embed"][batch["tokens"]]
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
            targets = labels[:, 1:]
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
            x = params["embed"][token][:, None, :]
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
