"""Traffic kind ``partpsp_train``: PartPSP training of an xLSTM stack.

One client drives ``Session.train`` (the engine driver, ``steps_per_call``
steps to a compiled segment, the privacy ``LedgerHook`` attached as the
trainer attaches it) on tokens of a seeded non-IID Markov stream,
``per_node_batch`` sequences of the configuration's ``context_length`` per
node and step. Set-up builds one session and state from the benchmark's
own weights, drives it through its first call of the window's own call and
feed (the comparison reads the state that call returns), and hands the
same session and state to the window. Mix parameters: ``per_node_batch``,
``steps_per_call``, ``stream``, ``trace_seconds`` and ``limits``.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from benchlib import compare, deploy, device, weights
from benchlib.data import TokenStream, make_batcher
from benchlib.harness import (CompileCounter, Outcome, free, hlo_text, span,
                              window)
from refs import partpsp as ref_partpsp


def xlstm_model(model: dict):
    """The program's model built at the configuration's sizes."""
    from repro.models import Transformer
    from repro.models.config import ModelConfig, XLSTMGroup

    d, h = model["d_model"], model["n_heads"]
    return Transformer(ModelConfig(
        name=model["name"], d_model=d, vocab_size=model["vocab_size"],
        n_heads=h, n_kv_heads=h, head_dim=int(d * model["proj_factor"]) // h,
        d_ff=0, tie_embedding=True, norm_eps=model["norm_eps"],
        groups=(XLSTMGroup(n_units=model["n_units"],
                           mlstm_per_unit=model["mlstm_per_unit"],
                           proj_factor=model["proj_factor"]),)))


def stream(cell) -> TokenStream:
    cfg, tr = cell.config, cell.traffic
    return TokenStream(cfg["model"]["vocab_size"], cfg["context_length"],
                       cfg["nodes"], tr["per_node_batch"], **tr["stream"])


def tokens_per_step(cell) -> int:
    return (cell.config["nodes"] * cell.traffic["per_node_batch"]
            * cell.config["context_length"])


def _diff_norms(a, b, scale: float = 1.0) -> list[float]:
    return [float(v) for v in jax.jit(
        lambda a, b: [jnp.sqrt(jnp.sum(jnp.square((x - y) * scale)))
                      for x, y in zip(a, b)])(a, b)]


def _node_sum_diff(a, b) -> np.ndarray:
    """sum_i (a_i - b) per leaf, as one row; ``b`` is one node's leaves."""
    row = jax.jit(lambda a, b: jnp.concatenate(
        [jnp.sum(x - y, axis=0).reshape(-1) for x, y in zip(a, b)]))(a, b)
    return np.asarray(row)


def _shared_grad_norms(lay, d_sum: np.ndarray, noise_sum: np.ndarray,
                       gamma_s: float) -> dict[str, float]:
    g = (d_sum.astype(np.float64) - noise_sum.astype(np.float64)) / -gamma_s
    out, off = {}, 0
    for p in lay.shared:
        n = int(np.prod(lay.shapes[p]))
        out[p] = float(np.sqrt(np.sum(np.square(g[off:off + n]))))
        off += n
    return out


def _check_layout(session, lay) -> None:
    """The program's partition must hold the leaves in the order the
    reference reads them (shared in wire order, then local)."""
    shared, local = session.partition.split(session.init_params)
    got = ([tuple(x.shape[1:]) for x in shared],
           [tuple(x.shape[1:]) for x in local])
    want = ([lay.shapes[p] for p in lay.shared],
            [lay.shapes[p] for p in lay.local])
    if got != want:
        raise ValueError(f"program partition {got} != benchmark layout {want}")


def drive(cell, seed: int, seconds: float, trace_dir: str | None,
          devs: list, t_start: float) -> Outcome:
    from repro.api import LedgerHook, Session
    from repro.core.partpsp import partpsp_init

    cfg, tr = cell.config, cell.traffic
    model, pp = cfg["model"], cfg["partpsp"]
    per_call = tr["steps_per_call"]
    k_w, k_data, k_run = jax.random.split(weights.seed_key(seed), 3)
    lay = ref_partpsp.layout(model, cfg["partition"]["shared"])
    make_batch = make_batcher(stream(cell), k_data)

    def batch_at(t: int):
        with span("batch_build"):
            return {"tokens": make_batch(t)}

    params = weights.xlstm_params(model, k_w, cfg["nodes"])
    # The session takes only the parameters' shapes: it would otherwise
    # hold a copy of them next to the state and the copy that
    # Session.train makes of it, which one chip cannot hold at this size.
    session = Session.build(
        deploy.topology(cfg), privacy=deploy.privacy(cfg),
        model=xlstm_model(model),
        partition=((cfg["partition"]["shared"], "shared"),),
        params_stacked=jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), params),
        algorithm=pp["algorithm"], gamma_l=pp["gamma_l"],
        gamma_s=pp["gamma_s"], clip=pp["clip"], schedule=cfg["schedule"],
        sync_interval=pp["sync_interval"], use_kernels=cfg["use_kernels"],
        chunk=per_call, packed=cfg["packed"], key=k_run)
    _check_layout(session, lay)
    ledger = LedgerHook()

    def call(held: list, start: int):
        """One call of ``per_call`` steps. ``Session.train`` copies the
        state it is given; ``held`` hands over the only reference, so the
        old state is freed as soon as it is copied."""
        with span("session_train"):
            return session.train(per_call, batch_at, state=held.pop(),
                                 start=start, hooks=(ledger,), key=k_run)

    def init_one():
        """Node 0's initial parameters, split as the state holds them
        (every node starts from the same ones)."""
        return session.partition.split(weights.xlstm_params(model, k_w, 1))

    # Set-up: the checked first call, through the window's own call and
    # feed; the readings come from the state it returns.
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
            prog = {"g_local": dict(zip(lay.local, _diff_norms(
                        l0, state.local, 1.0 / pp["gamma_l"]))),
                    "d_sum": _node_sum_diff(state.dpps.push.s, s0),
                    "change": dict(zip(lay.shared + lay.local, _diff_norms(
                        list(state.dpps.push.s) + list(state.local),
                        list(s0) + list(l0))))}
            del s0, l0, state
        # The second call warms the state copy at the window's own entry:
        # every call of the window reuses the runner and shapes above.
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
    hlo = ()
    if trace_dir:
        batches = {"tokens": jnp.stack(
            [make_batch(t + i) for i in range(per_call)])}
        hlo = (hlo_text(session.segment_runner((ledger,)), held[0], batches,
                        k_run),)
    del held, session
    free()

    ref = ref_train(cell, seed)
    numbers = compare.train_numbers(*train_observations(cell, prog, ref))
    numbers["window_compiles"] = float(compiles.count)
    return Outcome(
        setup_s=setup_s,
        metrics={"train_tokens_per_s": done * tokens_per_step(cell) / w_s},
        attempted=done, failed=failed, numbers=numbers,
        view={"steps": done}, device=dev, hlo_texts=hlo)


def ref_train(cell, seed: int, **variant) -> dict:
    """The reference's raw readings of the first call's steps from the
    seed: the local gradient sum (l0 - l) / gamma_l, the node-summed shared
    change and the node-summed scaled noise of those rounds, and every
    leaf's change.
    ``variant`` (``dtype``, ``mix_precision``, ``half_batch``, ``gossip``)
    computes the control or a planted fault instead."""
    cfg, tr = cell.config, cell.traffic
    model, pp, n = cfg["model"], cfg["partpsp"], cfg["nodes"]
    per_call = tr["steps_per_call"]
    k_w, k_data, k_run = jax.random.split(weights.seed_key(seed), 3)
    lay = ref_partpsp.layout(model, cfg["partition"]["shared"])
    make_batch = make_batcher(stream(cell), k_data)
    dtype = variant.get("dtype", "float32")
    # Node 0's start (every node starts the same) is all the readings need.
    r0 = ref_partpsp.held_in(
        ref_partpsp.init(weights.xlstm_params(model, k_w, 1), lay), dtype)
    rstate = ref_partpsp.held_in(
        ref_partpsp.init(weights.xlstm_params(model, k_w, n), lay), dtype)
    w = jnp.asarray(deploy.ref_weights(cell))
    local = lambda st: [st.local[p] for p in lay.local]  # noqa: E731
    noise_first = 0.0
    for step in range(per_call):
        rstate, _, noise = ref_partpsp.step(rstate, make_batch(step), k_run,
                                            cfg=cfg, lay=lay, w=w, **variant)
        noise_first = noise_first + np.asarray(noise, np.float64)
        del noise
    g_local = dict(zip(lay.local, _diff_norms(
        local(r0), local(rstate), 1.0 / pp["gamma_l"])))
    d_sum = np.asarray(jnp.sum(rstate.dpps.s - r0.dpps.s, axis=0))
    change = dict(zip(lay.shared, _row_leaf_norms(lay,
                                                  rstate.dpps.s - r0.dpps.s)))
    change.update(zip(lay.local, _diff_norms(local(rstate), local(r0))))
    return {"g_local": g_local, "d_sum": d_sum,
            "noise_first": noise_first, "change": change}


def train_observations(cell, prog: dict, ref: dict) -> tuple[dict, dict]:
    """Raw readings -> the compared observations of both sides. The
    shared gradient of either side is its node-summed state change over
    the first call less the clean reference's noise of those rounds."""
    cfg = cell.config
    lay = ref_partpsp.layout(cfg["model"], cfg["partition"]["shared"])
    gamma_s = cfg["partpsp"]["gamma_s"]

    def obs(raw):
        return {"change": raw["change"],
                "grad": {**raw["g_local"], **_shared_grad_norms(
                    lay, raw["d_sum"], ref["noise_first"], gamma_s)}}

    return obs(prog), obs(ref)


def _row_leaf_norms(lay, rows: jax.Array) -> list[float]:
    out, off = [], 0
    for p in lay.shared:
        k = int(np.prod(lay.shapes[p]))
        out.append(float(jnp.sqrt(jnp.sum(jnp.square(rows[:, off:off + k])))))
        off += k
    return out


def controls(cell, seed: int, **_):
    """(variant, numbers) for the control and the planted faults of one
    seed, each read against the clean reference: the model computed and
    its parameters stored in bfloat16 with the mix in three bfloat16
    passes (the control), half of each node's batch left out (the mean
    taken over the rest), the exchange between nodes left out. A step
    that returns its state unchanged reads 1 by construction."""
    clean = ref_train(cell, seed)
    variants = {
        "control": dict(dtype="bfloat16", mix_precision="high"),
        "half_batch": dict(half_batch=True),
        "no_exchange": dict(gossip=False),
    }
    for name, kw in variants.items():
        raw = ref_train(cell, seed, **kw)
        yield name, compare.train_numbers(
            *train_observations(cell, raw, clean))
