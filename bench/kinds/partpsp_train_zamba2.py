"""Traffic kind ``partpsp_train_zamba2``: PartPSP training of a Zamba2 share.

Driven as ``partpsp_train`` drives its xLSTM stack: one client calls
``Session.train`` (``rounds`` steps, each one DPPS round, to a compiled
segment; the privacy ``LedgerHook`` attached) on tokens of a seeded non-IID
Markov stream, ``per_node_batch`` sequences of the configuration's
``context_length`` per node and step. The program's model is the repo's
``Transformer`` with the published Zamba2 group, told which
tensor-parallel share it holds; its weights are the benchmark's own
(``benchlib.zamba2_params``). Set-up draws the tokens of the window's
steps in one call (a sequence is drawn one dependent token at a time,
which a data pipeline would have ready), builds one session and state,
drives the checked first call and a warm one through the window's own
call and feed, and hands the same session and state to the window.

The comparison reads the numbers ``partpsp_train`` reads, against the
plain reference (``refs/partpsp_zamba2.py``) over the first call: the
gradient the optimizer got, read back out of the nodes' parameters y
(``grad_gap``), and each leaf's change (``change_gap``), by the median
leaf (``benchlib.compare``); and the change of the shared leaves by their
own median (``shared_change_gap``). ``y_gap``, the one number limited, is
the largest of the three, each over its tolerance in the mix's
``tolerances``.
With ``--trace 1`` the device time under the model's ``model_*`` scopes
(``benchlib.spans.model_phase_s``) goes to the readers as
``view["model_phase_s"]``.
"""
from __future__ import annotations

import pathlib
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchlib import compare, deploy, device, manifest, spans, weights
from benchlib import zamba2_params
from benchlib.data import TokenStream, make_batcher
from benchlib.harness import (CompileCounter, Outcome, free, hlo_text, span,
                              window)
from benchlib.trace import load
from refs import partpsp as ref_partpsp
from refs import partpsp_zamba2 as ref_zamba2

# The kind partpsp_train's state readings, shared as they are.
_base = manifest.load_module(pathlib.Path(__file__).resolve().parents[1],
                             "kinds", "partpsp_train")


def zamba2_model(cfg: dict):
    """The program's model at the configuration's share: a ModelConfig of
    the whole layer (counts held times the share's size) that is told its
    share."""
    from repro.models import Transformer
    from repro.models.config import ModelConfig, ZambaGroup

    m, size = zamba2_params.held(cfg), cfg["share"]["size"]
    return Transformer(ModelConfig(
        name=cfg["name"], d_model=m["d_model"],
        vocab_size=m["vocab"] * size, n_heads=m["attn_heads"] * size,
        n_kv_heads=m["attn_heads"] * size, head_dim=m["attn_head_dim"],
        d_ff=cfg["intermediate_size"], activation=cfg["hidden_act"],
        norm_eps=m["eps"], rope_theta=m["rope_theta"], tie_embedding=True,
        share=(m["rank"], size),
        groups=(ZambaGroup(
            layers_block_type=m["layers"], num_mem_blocks=m["num_mem_blocks"],
            adapter_rank=m["adapter_rank"], mamba_headdim=m["mamba_headdim"],
            mamba_ngroups=m["mamba_groups"] * size, d_state=m["d_state"],
            d_conv=m["d_conv"], expand=cfg["mamba_expand"],
            chunk_size=m["chunk_size"], time_step_min=m["dt_min"]),)))


def stream(cell) -> TokenStream:
    cfg, tr = cell.config, cell.traffic
    return TokenStream(cfg["vocab_size"], cfg["context_length"], cfg["nodes"],
                       tr["per_node_batch"], **tr["stream"])


def tokens_per_step(cell) -> int:
    return (cell.config["nodes"] * cell.traffic["per_node_batch"]
            * cell.config["context_length"])


def batcher(cell, key, steps: int):
    """``batch_at(t)`` of ``make_batcher`` (the same tokens for the same
    key and t), with steps 0..steps-1 drawn in one vmapped call."""
    st = stream(cell)
    make_batch = make_batcher(st, key)
    tables = jax.jit(st.tables)(jax.random.fold_in(key, 0))
    step_key = jax.random.fold_in(key, 1)
    ahead = jax.jit(jax.vmap(st.batch, in_axes=(None, 0)))(
        tables, jax.vmap(lambda t: jax.random.fold_in(step_key, t))(
            jnp.arange(steps)))

    def batch_at(t: int):
        return ahead[t] if t < steps else make_batch(t)

    return batch_at


def _layout(cfg):
    return ref_zamba2.layout(zamba2_params.held(cfg),
                             cfg["partition"]["shared"])


def drive(cell, seed: int, seconds: float, trace_dir: str | None,
          devs: list, t_start: float) -> Outcome:
    from repro.api import LedgerHook, Session
    from repro.core.partpsp import partpsp_init

    cfg, tr = cell.config, cell.traffic
    pp, m = cfg["partpsp"], zamba2_params.held(cfg)
    per_call = tr["rounds"]
    model = zamba2_model(cfg)
    k_w, k_data, k_run = jax.random.split(weights.seed_key(seed), 3)
    lay = _layout(cfg)
    make_batch = batcher(cell, k_data, tr["steps_ahead"])

    def batch_at(t: int):
        with span("batch_build"):
            return {"tokens": make_batch(t)}

    params = zamba2_params.params(m, k_w, cfg["nodes"])
    # The session takes only the parameters' shapes (a copy next to the
    # state would not fit).
    session = Session.build(
        deploy.topology(cfg), privacy=deploy.privacy(cfg), model=model,
        partition=((cfg["partition"]["shared"], "shared"),),
        params_stacked=jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), params),
        algorithm=pp["algorithm"], gamma_l=pp["gamma_l"],
        gamma_s=pp["gamma_s"], clip=pp["clip"], schedule=cfg["schedule"],
        sync_interval=pp["sync_interval"], use_kernels=cfg["use_kernels"],
        chunk=per_call, packed=cfg["packed"], key=k_run)
    _base._check_layout(session, lay)
    ledger = LedgerHook()

    def call(held: list, start: int):
        """One call of ``per_call`` steps; ``held`` hands over the only
        reference to the state, so the old one is freed once copied."""
        with span("session_train"):
            return session.train(per_call, batch_at, state=held.pop(),
                                 start=start, hooks=(ledger,), key=k_run)

    def init_one():
        return session.partition.split(zamba2_params.params(m, k_w, 1))

    held = [partpsp_init(params, session.partition, session.train_cfg)]
    del params
    t = 0
    for _ in range(2):
        rep = call(held, t)
        held.append(rep.state)
        del rep
        if t == 0:
            s0, l0 = init_one()
            state = held[0]
            prog = {"g_local": dict(zip(lay.local, _base._diff_norms(
                        l0, state.local, 1.0 / pp["gamma_l"]))),
                    "d_sum": _base._node_sum_diff(state.dpps.push.s, s0),
                    "change": dict(zip(lay.shared + lay.local,
                                       _base._diff_norms(
                        list(state.dpps.push.s) + list(state.local),
                        list(s0) + list(l0))))}
            del s0, l0, state
        t += per_call
    jax.block_until_ready(held)
    setup_s = time.perf_counter() - t_start

    length = min(seconds, tr["trace_seconds"]) if trace_dir else seconds
    done = failed = 0
    with CompileCounter() as compiles, window(trace_dir):
        w0 = time.perf_counter()
        while True:
            rep = call(held, t)
            held.append(rep.state)
            t += per_call
            done += per_call
            failed += int(np.sum(~np.isfinite(rep.trajectory["loss_mean"])))
            del rep
            if time.perf_counter() - w0 >= length:
                break
        jax.block_until_ready(held)
        w_s = time.perf_counter() - w0
    dev = device.record(devs)
    hlo, view = (), {"steps": done}
    if trace_dir:
        batches = {"tokens": jnp.stack(
            [make_batch(t + i) for i in range(per_call)])}
        hlo = (hlo_text(session.segment_runner((ledger,)), held[0], batches,
                        k_run),)
        view["model_phase_s"] = spans.model_phase_s(
            load(trace_dir), n_devices=cell.chips, hlo_texts=hlo)
    del held, session, make_batch
    free()

    ref = ref_train(cell, seed)
    numbers = gaps(cell, *train_observations(cell, prog, ref))
    print("zamba2 check " + " ".join(
        f"{k} {numbers[k]!r} tolerance {tr['tolerances'][k]!r}"
        for k in tr["tolerances"]), file=sys.stderr)
    numbers = {"y_gap": numbers["y_gap"],
               "window_compiles": float(compiles.count)}
    return Outcome(
        setup_s=setup_s,
        metrics={"train_tokens_per_s": done * tokens_per_step(cell) / w_s},
        attempted=done, failed=failed, numbers=numbers, view=view,
        device=dev, hlo_texts=hlo)


def ref_train(cell, seed: int, **variant) -> dict:
    """The reference's raw readings of the first call's steps from the
    seed, as ``partpsp_train.ref_train`` reads them. ``variant``
    (``dtype``, ``mix_precision``, ``half_batch``, ``gossip``) computes the
    control or a planted fault instead."""
    cfg, tr = cell.config, cell.traffic
    pp, n, m = cfg["partpsp"], cfg["nodes"], zamba2_params.held(cfg)
    k_w, k_data, k_run = jax.random.split(weights.seed_key(seed), 3)
    lay = _layout(cfg)
    make_batch = make_batcher(stream(cell), k_data)
    dtype = variant.get("dtype", "float32")
    # ``held`` hands the step the only reference to the state, so the step
    # frees the old local leaves before its round.
    held = [ref_partpsp.held_in(
        ref_partpsp.init(zamba2_params.params(m, k_w, n), lay), dtype)]
    w = jnp.asarray(deploy.ref_weights(cell))
    noise_first = 0.0
    for step in range(tr["rounds"]):
        rstate, _, noise = ref_zamba2.step(
            held.pop(), make_batch(step), k_run, cfg=cfg, model=m, lay=lay,
            w=w, **variant)
        held.append(rstate)
        del rstate
        noise_first = noise_first + np.asarray(noise, np.float64)
        del noise
    rstate = held.pop()
    # Node 0's start (every node starts the same), made after the steps so
    # that it is not held through them.
    r0 = ref_partpsp.held_in(
        ref_partpsp.init(zamba2_params.params(m, k_w, 1), lay), dtype)
    local = lambda st: [st.local[p] for p in lay.local]  # noqa: E731
    g_local = dict(zip(lay.local, _base._diff_norms(
        local(r0), local(rstate), 1.0 / pp["gamma_l"])))
    d_sum = np.asarray(jnp.sum(rstate.dpps.s - r0.dpps.s, axis=0))
    change = dict(zip(lay.shared, _base._row_leaf_norms(
        lay, rstate.dpps.s - r0.dpps.s)))
    change.update(zip(lay.local, _base._diff_norms(local(rstate), local(r0))))
    return {"g_local": g_local, "d_sum": d_sum,
            "noise_first": noise_first, "change": change}


def train_observations(cell, prog: dict, ref: dict) -> tuple[dict, dict]:
    """Raw readings -> the compared observations of both sides (as
    ``partpsp_train.train_observations``)."""
    lay = _layout(cell.config)
    gamma_s = cell.config["partpsp"]["gamma_s"]

    def obs(raw):
        return {"change": raw["change"],
                "grad": {**raw["g_local"], **_base._shared_grad_norms(
                    lay, raw["d_sum"], ref["noise_first"], gamma_s)}}

    return obs(prog), obs(ref)


def gaps(cell, prog: dict, ref: dict) -> dict[str, float]:
    """``grad_gap`` and ``change_gap`` (``compare.train_numbers``), the
    change gap over the shared leaves alone (``shared_change_gap``: the
    round moves only them, and they are not the median leaf of all), and
    ``y_gap``, the largest of the three over its tolerance."""
    out = compare.train_numbers(prog, ref)
    shared = set(_layout(cell.config).shared)
    out["shared_change_gap"] = compare.median_gap(
        prog["change"], ref["change"],
        [k for k in compare.moving_leaves(ref["grad"]) if k in shared])
    tol = cell.traffic["tolerances"]
    out["y_gap"] = max(out[k] / tol[k] for k in tol)
    return out


def controls(cell, seed: int, **_):
    """(variant, numbers) for the control and the planted faults of one
    seed, read against the clean reference: the model computed and its
    parameters stored in bfloat16 with the mix in three bfloat16 passes
    (the control), each node reading only the first half of its tokens,
    the exchange between nodes left out."""
    clean = ref_train(cell, seed)
    variants = {
        "control": dict(dtype="bfloat16", mix_precision="high"),
        "half_batch": dict(half_batch=True),
        "no_exchange": dict(gossip=False),
    }
    for name, kw in variants.items():
        # Each variant compiles its own reference; the ones before it would
        # otherwise keep their device memory.
        free()
        raw = ref_train(cell, seed, **kw)
        yield name, gaps(cell, *train_observations(cell, raw, clean))
