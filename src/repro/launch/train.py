"""PartPSP training driver.

Runs the full decentralized DP training loop on whatever devices exist:
on a CPU it runs reduced configs end-to-end (the examples use it); on one
TPU chip it trains full-width configs (``chip_smoke.py`` drives
xlstm-125m); on a real fleet the same code paths run on the production
mesh. Pallas kernels route by platform unless --use-kernels /
--no-use-kernels says otherwise.

    PYTHONPATH=src python -m repro.launch.train --arch llama3.2-1b \
        --reduced --nodes 8 --steps 50 --algorithm partpsp

Key flags mirror the paper's experimental grid: --algorithm
{partpsp,sgp,sgpdp,pedfl}, --b (privacy budget), --gamma-n, --topology
{dout,exp,ring,full,er,matching,torus,smallworld} (the repro.api.cli
registry; random families take --graph-seed / --er-p / --matchings /
--resample-period), --degree, --sync-interval, --schedule
{dense,circulant}. Network fault injection (repro.net): --drop-rate /
--straggler-rate / --churn attach a FaultModel — the engine masks the
realized W inside the scan and the ledger records realized out-degrees.
Bounded-delay asynchrony (repro.net.delays): --max-delay /
--timeout-rate / --node-rates attach a DelayModel — messages ride
per-edge mailboxes inside the scan, stale ones time out back to the
sender, and the ledger records per-round staleness/participation.

The driver is a thin shell over the session front door
(:mod:`repro.api`): :func:`build_session` assembles the arch-specific
model + partition rules and hands everything protocol-shaped to
``Session.build``; the run itself is ``session.train`` with the
cross-cutting concerns attached as hooks — the streaming privacy ledger
(--ledger-out), epsilon-budget enforcement (--privacy-budget /
--strict-budget) and metric logging are ``LedgerHook`` / ``BudgetHook`` /
``MetricsHook`` instances, not driver code.

Execution drivers (--driver):

* ``engine`` (default) — the scan-compiled engine (repro.engine): training
  runs in --chunk-round segments, each one XLA dispatch, with per-round
  metrics captured inside the scan and checkpoints on segment boundaries.
* ``loop``   — the per-round Python loop (one dispatch per round). Kept as
  the reference path; tests/test_engine.py pins that both produce identical
  trajectories for the same seed.
"""
from __future__ import annotations

import argparse
import json
import time

import jax

from repro.api import (
    BudgetHook,
    LedgerHook,
    MetricsHook,
    PrivacySpec,
    RunReport,
    Session,
    add_delay_arguments,
    add_fault_arguments,
    add_protocol_arguments,
    add_topology_arguments,
    delays_from_args,
    faults_from_args,
    make_topology as _registry_topology,
    topology_from_args,
    validate_protocol_args,
    wire_from_args,
)
from repro.configs import ARCH_NAMES, get_config
from repro.data import NodeShardedLoader, SyntheticLMStream
from repro.launch.compile_cache import enable_compile_cache
from repro.models import Transformer


def make_topology(kind: str, n_nodes: int, degree: int):
    """Back-compat veneer over the shared registry (repro.api.cli)."""
    return _registry_topology(kind, n_nodes, degree=degree)


def build_session(arch_name: str, *, reduced: bool, n_nodes: int,
                  algorithm: str, b: float, gamma_n: float, gamma_l: float,
                  gamma_s: float, clip: float, topology, degree: int = 2,
                  sync_interval: int = 5, schedule: str = "dense",
                  use_kernels: bool | None = None, seed: int = 0,
                  chunk: int = 50,
                  packed: bool = True, wire_dtype: str = "f32", faults=None,
                  delays=None, wire=None):
    """Arch-specific assembly -> one protocol session (the front door).

    Owns only what is genuinely arch-shaped — model construction and the
    shared/local partition rules per algorithm (full sharing for
    SGP/SGPDP, split-point clamping for the 2-layer smoke stacks); every
    protocol decision lives in ``Session.build``. ``topology`` is a
    registry name (repro.api.cli) or an already-built Topology;
    ``faults`` attaches a repro.net FaultModel, ``delays`` a repro.net
    DelayModel (bounded-delay asynchronous push-sum).
    """
    arch = get_config(arch_name)
    model_cfg = arch.smoke if reduced else arch.model
    model = Transformer(model_cfg)
    topo = (topology if not isinstance(topology, str)
            else make_topology(topology, n_nodes, degree))

    rules = arch.shared_rules if algorithm != "sgpdp" else ((".*", "shared"),)
    if algorithm == "sgp":
        rules = ((".*", "shared"),)
    if reduced:
        # smoke configs have 2-layer stacks: clamp split points accordingly
        rules = tuple(
            (pat, ("split_layers", 1) if isinstance(act, tuple) else act)
            for pat, act in rules)

    session = Session.build(
        topo, privacy=PrivacySpec(b=b, gamma_n=gamma_n), model=model,
        partition=rules, algorithm=algorithm, gamma_l=gamma_l,
        gamma_s=gamma_s, clip=clip, schedule=schedule,
        sync_interval=sync_interval, use_kernels=use_kernels, chunk=chunk,
        packed=packed, wire_dtype=wire_dtype, faults=faults, delays=delays,
        wire=wire, seed=seed)
    return model, model_cfg, session


def build_trainer(arch_name: str, **kwargs):
    """Per-round reference driver: a jitted single-step function.

    Compatibility veneer over the session API (the seed repo's public
    shape); returns ``(model, model_cfg, topo, cfg, partition, state,
    step)`` with round-0 mixing operands bound into ``step``.
    """
    model, model_cfg, session = build_session(arch_name, **kwargs)
    return (model, model_cfg, session.topology, session.train_cfg,
            session.partition, session.train_state(), session.step_fn())


def build_engine_trainer(arch_name: str, *, chunk: int = 50,
                         packed: bool = True, wire_dtype: str = "f32",
                         **kwargs):
    """Scan-engine driver veneer over the session API.

    Returns ``(model, model_cfg, topo, cfg, partition, state, run_chunk,
    plan)`` where ``run_chunk(state, batches, base_key)`` advances one
    donated, scan-compiled segment — see ``Session.segment_runner``.
    """
    model, model_cfg, session = build_session(
        arch_name, chunk=chunk, packed=packed, wire_dtype=wire_dtype,
        **kwargs)
    return (model, model_cfg, session.topology, session.train_cfg,
            session.partition, session.train_state(),
            session.segment_runner(), session.plan)


def main(argv: list[str] | None = None) -> RunReport:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", choices=ARCH_NAMES, default="llama3.2-1b")
    ap.add_argument("--reduced", action="store_true",
                    help="use the smoke-scale config (CPU friendly)")
    ap.add_argument("--nodes", type=int, default=8)
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--per-node-batch", type=int, default=4)
    ap.add_argument("--seq-len", type=int, default=64)
    ap.add_argument("--algorithm", choices=("partpsp", "sgp", "sgpdp", "pedfl"),
                    default="partpsp")
    ap.add_argument("--b", type=float, default=3.0)
    ap.add_argument("--gamma-n", type=float, default=0.003)
    ap.add_argument("--gamma-l", type=float, default=0.05)
    ap.add_argument("--gamma-s", type=float, default=0.05)
    ap.add_argument("--clip", type=float, default=100.0)
    add_topology_arguments(ap)
    add_fault_arguments(ap)
    add_delay_arguments(ap)
    ap.add_argument("--sync-interval", type=int, default=5)
    ap.add_argument("--schedule", choices=("dense", "circulant", "sparse"),
                    default="dense")
    ap.add_argument("--use-kernels", action=argparse.BooleanOptionalAction,
                    default=None,
                    help="Pallas kernels on/off (default: on iff the "
                         "backend is TPU)")
    ap.add_argument("--driver", choices=("engine", "loop"), default="engine",
                    help="scan-compiled engine segments vs per-round loop")
    add_protocol_arguments(ap)
    ap.add_argument("--seed", type=int, default=2024)   # paper's seed
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--checkpoint", default=None)
    ap.add_argument("--metrics-out", default=None)
    ap.add_argument("--ledger-out", default=None,
                    help="stream the per-round privacy ledger to this JSONL")
    ap.add_argument("--privacy-budget", type=float, default=None,
                    help="total epsilon ceiling for the run")
    ap.add_argument("--strict-budget", action="store_true",
                    help="abort training once --privacy-budget is exceeded")
    args = ap.parse_args(argv)
    enable_compile_cache()
    validate_protocol_args(ap, args)
    topo = topology_from_args(ap, args, args.nodes)
    faults = faults_from_args(ap, args, n_nodes=args.nodes)
    delays = delays_from_args(ap, args, n_nodes=args.nodes)
    wire = wire_from_args(ap, args)
    if delays is not None and args.sync_interval:
        ap.error("--max-delay/--timeout-rate/--node-rates need "
                 "--sync-interval 0: a synchronization round would average "
                 "exact values while mass is still in flight in mailboxes")
    if delays is not None and args.schedule == "circulant":
        ap.error("--max-delay/--timeout-rate/--node-rates need --schedule "
                 "dense or sparse: the mailbox runtime consumes per-round "
                 "weight operands, not circulant offsets")
    if args.schedule == "circulant" and topo.offsets(0) is None:
        ap.error(f"--topology {args.topology} is not circulant "
                 f"({type(topo).__name__} has no offset structure); use "
                 "--schedule dense")
    if faults is not None and args.schedule == "circulant":
        ap.error("--drop-rate/--straggler-rate need --schedule dense or "
                 "sparse: masked edges break circulant structure (dense "
                 "switches to the dynamic schedule internally; sparse "
                 "masks its edge list in place)")

    model, model_cfg, session = build_session(
        args.arch, reduced=args.reduced, n_nodes=args.nodes,
        algorithm=args.algorithm, b=args.b, gamma_n=args.gamma_n,
        gamma_l=args.gamma_l, gamma_s=args.gamma_s, clip=args.clip,
        topology=topo, sync_interval=args.sync_interval,
        schedule=args.schedule, use_kernels=args.use_kernels,
        seed=args.seed, chunk=args.chunk, packed=args.packed,
        faults=faults, delays=delays, wire=wire)
    partition = session.partition

    wire_name = wire.name if wire is not None else "f32"
    mode = (f"packed/{wire_name}" if args.driver == "engine"
            and args.packed else "pytree")
    print(f"arch={args.arch} ({'reduced' if args.reduced else 'FULL'}) "
          f"algorithm={args.algorithm} nodes={args.nodes} topo={args.topology}"
          f"(d={args.degree}) driver={args.driver}[{mode}] "
          f"d_s={partition.d_shared():,} d_l={partition.d_local():,}")

    stream = SyntheticLMStream(vocab_size=model_cfg.vocab_size,
                               seq_len=args.seq_len, n_nodes=args.nodes,
                               seed=args.seed)
    loader = NodeShardedLoader(stream, per_node_batch=args.per_node_batch,
                               seed=args.seed)

    def batch_at(t: int):
        batch = loader.batch_at(t)
        if model_cfg.input_mode == "embeddings":
            toks = batch["tokens"]
            key_e = jax.random.fold_in(jax.random.PRNGKey(7), t)
            batch = {"embeds": jax.random.normal(
                        key_e, toks.shape + (model_cfg.d_model,)) * 0.1,
                     "labels": toks}
        return batch

    t0 = time.time()
    metrics = MetricsHook(
        fields={"loss": "loss_mean", "sensitivity": "sensitivity_used",
                "grad_l1_max": "grad_l1_max"},
        log_every=args.log_every, total=args.steps,
        formatter=lambda r: (f"step {r['step']:5d} loss={r['loss']:.4f} "
                             f"S={r['sensitivity']:.3f} "
                             f"({(time.time()-t0)/(r['step']+1):.2f}s/step)"))
    ledger = LedgerHook(path=args.ledger_out, budget=args.privacy_budget)
    hooks = [ledger, metrics]
    if args.privacy_budget is not None:
        note = (" (engine driver enforces at segment granularity)"
                if args.driver == "engine" else "")
        hooks.append(BudgetHook(args.privacy_budget,
                                strict=args.strict_budget, note=note))

    report = session.train(args.steps, batch_at, hooks=hooks,
                           key=jax.random.PRNGKey(args.seed),
                           driver=args.driver)

    print("privacy:", json.dumps(ledger.summary()))
    if args.ledger_out:
        print("privacy ledger written to", args.ledger_out)
    if args.metrics_out:
        with open(args.metrics_out, "w") as f:
            json.dump(metrics.history, f, indent=1)
    if args.checkpoint and not report.aborted:
        # consensus shared params are identical across nodes; persist node
        # 0's view (s-bar + its personalized local params) for serving
        session.save_consensus(args.checkpoint, report.state,
                               step=report.rounds,
                               metadata={"arch": args.arch,
                                         "algorithm": args.algorithm})
        print("checkpoint written to", args.checkpoint)
    if report.aborted:
        if args.checkpoint:
            # the whole point of strict mode is that over-budget parameters
            # are never released — including via the serving checkpoint
            print("checkpoint NOT written (over budget):", args.checkpoint)
        raise SystemExit(
            "aborted: privacy budget exhausted (--strict-budget)")
    return report


if __name__ == "__main__":
    main()
