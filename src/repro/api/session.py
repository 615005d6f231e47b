"""ProtocolSession — the typed front door of the reproduction.

Every consumer used to hand-wire the same setup block: topology ->
``calibrate_constants`` -> config -> ``ProtocolPlan`` -> packed layout ->
jitted segment runner -> ``run_segments`` loop (launch/train.py,
benchmarks/common.py, all four examples carried their own copy).
:meth:`Session.build` owns that block once:

* constant calibration — (C', lambda) from the topology unless the
  :class:`PrivacySpec` pins them (the paper's per-setup tuning, SV.B);
* plan derivation — :class:`repro.engine.ProtocolPlan` from the topology
  (+ mesh) with the deployment knobs (schedule, packed runtime, wire
  dtype, sync cadence, chunking) in one place;
* config stamping — the plan's choices stamped onto
  ``DPPSConfig`` / ``PartPSPConfig`` exactly once;
* base-key / fold-in discipline — one base key; the engine folds the
  absolute round counter carried in the state, so loop and engine drivers
  produce bit-identical trajectories and checkpoints resume the same
  noise stream;
* checkpoint / resume — full-state and consensus-view checkpoints through
  ``repro.checkpoint``.

The run methods return typed :class:`repro.api.results.RunReport` /
:class:`ServeReport` objects, and observers attach as
:class:`repro.api.hooks.RoundHook` pipelines: scan-side ``capture`` adds
trajectory leaves, host-side ``consume`` runs at segment boundaries
(ledger streaming, budget enforcement, logging, transcripts). A hookless
session compiles to HLO identical to the bare engine (pinned in
tests/test_api.py) — the front door costs nothing.

Typical use::

    from repro.api import Session, PrivacySpec, LedgerHook

    session = Session.build(DOutGraph(n_nodes=10, d=2),
                            privacy=PrivacySpec(b=5.0, gamma_n=1e-3))
    report = session.run(200, values=private_values,
                         hooks=[LedgerHook(path="ledger.jsonl")])
    consensus = session.consensus(report.state)
"""
from __future__ import annotations

import dataclasses
import functools
import time
from typing import Any, Callable, Iterable, Iterator, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.api.hooks import (
    RoundHook,
    RunAbort,
    RunContext,
    capture_rows,
    hook_trace_spec,
)
from repro.api.results import RunReport, ServeReport, estimate_wire_bytes
from repro.checkpoint import load_checkpoint, save_checkpoint
from repro.core.dpps import DPPSConfig, DPPSState, dpps_init, dpps_step
from repro.core.dpps import dpps_consensus as _dpps_consensus
from repro.core.dpps import is_sync_round
from repro.core.partition import Partition
from repro.core.partpsp import (
    PartPSPConfig,
    PartPSPState,
    consensus_params,
    make_baseline_config,
    partpsp_init,
    partpsp_step,
)
from repro.core.topology import Topology, calibrate_constants
from repro.core.tree_utils import PyTree
from repro.engine import (
    ProtocolPlan,
    run_decode,
    run_dpps,
    run_partpsp,
    run_segments,
    stack_rounds,
)
from repro.obs.trace import (
    SPAN_API_CONSENSUS,
    SPAN_API_COPY_STATE,
    SPAN_API_HOOKS,
    SPAN_API_REPORT,
    SPAN_API_RUN,
    SPAN_API_STATE_INIT,
    SPAN_API_TRAIN,
    SPAN_API_WAIT,
    SPAN_ENGINE_DISPATCH,
    SPAN_ENGINE_INPUTS,
    compile_count,
    span,
)

__all__ = ["PrivacySpec", "ProtocolSession", "Session"]


@dataclasses.dataclass(frozen=True)
class PrivacySpec:
    """The privacy side of a session, separated from deployment choices.

    ``c_prime`` / ``lam`` default to ``None`` — calibrated to the
    topology's mixing contraction by :func:`calibrate_constants` (the
    principled version of the paper's per-setup tuning). ``mechanism``
    names (or is) a :class:`repro.audit.mechanisms.NoiseMechanism`
    replacing the Eq.-8 Laplace draw; ``None`` keeps the built-in draw
    (bit-identical to ``LaplaceMechanism``).
    """

    b: float = 5.0
    gamma_n: float = 1.0
    noise: bool = True
    c_prime: float | None = None
    lam: float | None = None
    sensitivity_mode: str = "estimated"
    fixed_sensitivity: float = 0.0
    mechanism: Any = None

    def resolve_mechanism(self) -> Any:
        if isinstance(self.mechanism, str):
            from repro.audit.mechanisms import get_mechanism

            return get_mechanism(self.mechanism)
        return self.mechanism


def _own_buffers(state: Any) -> Any:
    """Fresh buffers for every leaf of ``state``.

    The segment runners donate their state argument (XLA aliases the
    packed carry in place); without this copy the *caller's* arrays —
    the ``values=`` tree a consensus state was built over, or the
    session's own ``init_params`` — would be the donated buffers and die
    with the first dispatch.
    """
    return jax.tree_util.tree_map(
        lambda x: x.copy() if hasattr(x, "copy") else x, state)


def _copy_to_host_async(tree: Any) -> None:
    """Start the device-to-host copy of every device leaf of ``tree``
    without waiting: the copies queue behind the work that makes them,
    and a later read finds them done or in flight."""
    for x in jax.tree_util.tree_leaves(tree):
        if isinstance(x, jax.Array):
            x.copy_to_host_async()


def _broadcast_nodes(params: PyTree, n_nodes: int) -> PyTree:
    """Single-node params -> node-stacked (every node starts identical).

    ``+ 0.0`` forces a fresh buffer per leaf so XLA never aliases the
    broadcast view into donated protocol carries.
    """
    return jax.tree_util.tree_map(
        lambda x: jnp.broadcast_to(x[None], (n_nodes,) + x.shape) + 0.0,
        params)


@dataclasses.dataclass(frozen=True, eq=False)
class ProtocolSession:
    """A frozen, fully-derived protocol deployment (see module docstring).

    Build with :meth:`build`; all fields are consistent by construction —
    ``cfg`` and ``train_cfg`` are already plan-stamped, ``partition`` is
    materialized, ``init_params`` are node-stacked. Serve-only sessions
    (``topology=None``) carry a model but no protocol.
    """

    topology: Topology | None
    plan: ProtocolPlan | None
    cfg: DPPSConfig | None               # resolved consensus/protocol config
    train_cfg: PartPSPConfig | None      # resolved training config (or None)
    partition: Partition | None
    model: Any
    loss_fn: Callable | None
    mechanism: Any
    init_params: PyTree | None           # node-stacked initial parameters
    base_key: jax.Array
    algorithm: str
    n_nodes: int

    # -- construction --------------------------------------------------------

    @classmethod
    def build(
        cls,
        topology: Topology | None = None,
        privacy: PrivacySpec | None = None,
        plan: ProtocolPlan | None = None,
        model: Any = None,
        partition: Any = None,
        *,
        params: PyTree | None = None,
        params_stacked: PyTree | None = None,
        algorithm: str = "partpsp",
        gamma_l: float = 0.05,
        gamma_s: float = 0.05,
        clip: float = 100.0,
        schedule: str | None = None,
        sync_interval: int | str | None = None,
        use_kernels: bool | None = None,
        chunk: int = 50,
        packed: bool = True,
        wire_dtype: str = "f32",
        mesh: Any = None,
        faults: Any = None,
        delays: Any = None,
        wire: Any = None,
        seed: int = 0,
        key: jax.Array | None = None,
    ) -> "ProtocolSession":
        """Derive a complete session from topology + privacy + deployment.

        ``privacy`` is a :class:`PrivacySpec` (default: the spec's
        defaults). ``plan`` overrides derivation — when ``None`` it is
        built from the topology with the deployment kwargs (``schedule``,
        ``sync_interval``, ``use_kernels``, ``chunk``, ``packed``,
        ``wire_dtype``, ``mesh``).

        ``model`` makes the session trainable/servable: a bare callable is
        taken as the loss function; an object contributes ``loss_fn`` and
        (for serving) ``prefill`` / ``init_cache`` / ``decode_step``, and
        its ``init(key)`` seeds ``params`` when none are given.
        ``partition`` is a :class:`Partition` or a rules tuple resolved
        against the node-stacked params; ``params`` are single-node
        (broadcast to every node) — pass ``params_stacked`` instead when
        they already carry the leading node axis.

        ``key`` (default ``PRNGKey(seed)``) is both the parameter-init key
        and the run drivers' base key; override per run with
        ``run(..., key=)``.

        ``faults`` (a :class:`repro.net.faults.FaultModel`) attaches
        network fault injection: an active model switches the derived plan
        onto the ``dynamic`` schedule (per-round W masked and
        column-renormalized inside the compiled scan) and the run
        trajectory/ledger record the *realized* out-degrees. Attach a
        :class:`repro.net.stats.NetworkStatsHook` to a run to get the
        realized-network record on ``RunReport.network``.

        ``delays`` (a :class:`repro.net.delays.DelayModel`) attaches the
        bounded-delay async runtime: the engine carries a message mailbox
        through the scan, each sent message gets a seeded random delay
        (timeouts re-credit the sender's self-loop; heterogeneous node
        rates hold skipped nodes), and the per-round
        staleness/timeout/participation stats join the trajectory. An
        inactive model is dropped — the session then runs the synchronous
        program bit-for-bit. Composes with ``faults``.

        ``wire`` (a :class:`repro.wire.WireCodec`) attaches wire
        compression: messages are encoded strictly *after* DP noise
        injection (noise-then-compress — the epsilon accounting is
        untouched) and the byte accounting everywhere (``RunReport``,
        ledger, network stats) reflects the compressed payload. An
        inactive/identity codec is dropped — the session then runs the
        raw f32 wire bit-for-bit. Value codecs compose with ``delays``.
        """
        spec = PrivacySpec() if privacy is None else privacy
        base_key = jax.random.PRNGKey(seed) if key is None else key
        mechanism = spec.resolve_mechanism()

        loss_fn = getattr(model, "loss_fn",
                          model if callable(model) else None)

        cfg = train_cfg = None
        part = None
        stacked = None
        n_nodes = 0
        if topology is not None:
            n_nodes = topology.n_nodes
            if spec.c_prime is None or spec.lam is None:
                cal_c, cal_l = calibrate_constants(topology)
            c_prime = spec.c_prime if spec.c_prime is not None else cal_c
            lam = spec.lam if spec.lam is not None else cal_l
            if plan is None:
                plan = ProtocolPlan.from_topology(
                    topology, mesh=mesh, schedule=schedule,
                    use_kernels=use_kernels, sync_interval=sync_interval,
                    chunk=chunk, packed=packed, wire_dtype=wire_dtype,
                    faults=faults, delays=delays, wire=wire)
            elif faults is not None:
                raise ValueError(
                    "pass faults= either to Session.build (plan derived) or "
                    "to ProtocolPlan.from_topology — not alongside an "
                    "explicit plan=, which already fixed the schedule")
            elif delays is not None:
                raise ValueError(
                    "pass delays= either to Session.build (plan derived) or "
                    "to ProtocolPlan.from_topology — not alongside an "
                    "explicit plan=, which already fixed the schedule")
            elif wire is not None and getattr(wire, "active", False):
                raise ValueError(
                    "pass wire= either to Session.build (plan derived) or "
                    "to ProtocolPlan.from_topology — not alongside an "
                    "explicit plan=, which already fixed the wire format")
            cfg_sync = sync_interval if isinstance(sync_interval, int) else 0

            # The protocol config knows dense/circulant/sparse; "dynamic"
            # is the engine-level fault-masking schedule (dense at step
            # level; a fault-masked sparse plan stays "sparse" throughout).
            cfg_schedule = ("dense" if plan.schedule == "dynamic"
                            else plan.schedule)
            if loss_fn is not None:
                train_cfg = make_baseline_config(
                    algorithm, gamma_l=gamma_l, gamma_s=gamma_s, clip=clip,
                    b=spec.b, gamma_n=spec.gamma_n, c_prime=c_prime, lam=lam,
                    schedule=cfg_schedule, sync_interval=cfg_sync,
                    sensitivity_mode=spec.sensitivity_mode)
                if not spec.noise and algorithm not in ("sgp",):
                    train_cfg = dataclasses.replace(
                        train_cfg, dpps=dataclasses.replace(
                            train_cfg.dpps, noise=False))
                if spec.sensitivity_mode == "fixed" and algorithm != "pedfl":
                    # make_baseline_config carries no fixed-scale knob
                    # (pedfl derives its own 2C); without this stamp a
                    # fixed-mode run would calibrate noise to scale 0.
                    train_cfg = dataclasses.replace(
                        train_cfg, dpps=dataclasses.replace(
                            train_cfg.dpps,
                            fixed_sensitivity=spec.fixed_sensitivity))
                train_cfg = plan.resolve_partpsp(train_cfg)
                cfg = train_cfg.dpps
            else:
                cfg = plan.resolve_dpps(DPPSConfig(
                    b=spec.b, gamma_n=spec.gamma_n, noise=spec.noise,
                    c_prime=c_prime, lam=lam, sync_interval=cfg_sync,
                    sensitivity_mode=spec.sensitivity_mode,
                    fixed_sensitivity=spec.fixed_sensitivity))

            if params_stacked is not None:
                stacked = params_stacked
            elif params is not None:
                stacked = _broadcast_nodes(params, n_nodes)
            elif model is not None and hasattr(model, "init"):
                stacked = _broadcast_nodes(model.init(base_key), n_nodes)

            if stacked is not None and loss_fn is not None:
                if partition is None:
                    partition = ((".*", "shared"),)
                part = (partition if isinstance(partition, Partition)
                        else Partition.from_rules(stacked, tuple(partition),
                                                  default="local"))

        return cls(topology=topology, plan=plan, cfg=cfg,
                   train_cfg=train_cfg, partition=part, model=model,
                   loss_fn=loss_fn, mechanism=mechanism, init_params=stacked,
                   base_key=base_key, algorithm=algorithm, n_nodes=n_nodes)

    # -- state ---------------------------------------------------------------

    def _require_protocol(self) -> None:
        if self.cfg is None or self.plan is None:
            raise ValueError(
                "this session has no protocol (built without a topology); "
                "Session.build(topology=...) enables run()/train()")

    def _attach_mail(self, dpps_state: DPPSState) -> DPPSState:
        """Async sessions carry the message Mailbox from round 0, so every
        segment (and checkpoint) shares one pytree structure — the engine
        would otherwise attach it on first dispatch and recompile."""
        delays = getattr(self.plan, "delays", None)
        if delays is not None and not dpps_state.mail:
            dpps_state = dpps_state._replace(
                mail=delays.init_mailbox(dpps_state.push.s))
        return dpps_state

    def consensus_state(self, values: PyTree) -> DPPSState:
        """Protocol state over per-node private ``values`` (node-stacked)."""
        self._require_protocol()
        return self._attach_mail(dpps_init(values, self.cfg))

    def train_state(self) -> PartPSPState:
        """Fresh PartPSP state from the session's initial parameters."""
        self._require_protocol()
        if self.partition is None or self.init_params is None:
            raise ValueError(
                "training needs model=/params= and partition= at build time")
        state = partpsp_init(self.init_params, self.partition, self.train_cfg)
        return state._replace(dpps=self._attach_mail(state.dpps))

    def consensus(self, state: DPPSState) -> PyTree:
        """Protocol output s-bar (Alg. 1 Output) from a consensus run."""
        with span(SPAN_API_CONSENSUS, call=self._calls):
            return _dpps_consensus(state)

    def consensus_view(self, state: PartPSPState, node: int = 0) -> PyTree:
        """Evaluation/serving params: network-average shared (s-bar) merged
        with ``node``'s personalized local parameters (paper SV.D)."""
        cp = consensus_params(state, self.partition)
        return jax.tree_util.tree_map(lambda x: x[node], cp)

    # -- compiled runners (exposed for HLO pins and power users) -------------

    def _cached_runner(self, kind: str, hooks: tuple, build):
        """Memoize jitted runners per (driver kind, hook pipeline).

        jax.jit's dispatch cache lives on the returned wrapper, so
        rebuilding it every ``run()``/``train()`` would recompile the
        whole scan segment on each call of a reused session. The key
        holds the hook objects themselves (identity-hashed and kept
        alive), so the hookless fast path always hits and a stale id can
        never alias a new pipeline.
        """
        cache = self.__dict__.get("_runners")
        if cache is None:
            cache = {}
            object.__setattr__(self, "_runners", cache)
        key = (kind, hooks)
        if key not in cache:
            cache[key] = build()
        return cache[key]

    def consensus_runner(self, hooks: Sequence[RoundHook] = ()):
        """The jitted segment function :meth:`run` drives. The incoming
        state is donated — XLA aliases the packed carry in place."""
        self._require_protocol()
        hooks = tuple(hooks)
        return self._cached_runner("dpps", hooks, lambda: jax.jit(
            functools.partial(run_dpps, cfg=self.cfg, plan=self.plan,
                              hooks=hooks, mechanism=self.mechanism),
            static_argnames=("rounds",), donate_argnums=(0,)))

    def segment_runner(self, hooks: Sequence[RoundHook] = ()):
        """The jitted training segment function :meth:`train` drives
        (``run_chunk(state, batches, base_key)``; state donated)."""
        self._require_protocol()
        if self.loss_fn is None:
            raise ValueError("training needs model= at build time")
        hooks = tuple(hooks)
        return self._cached_runner("partpsp", hooks, lambda: jax.jit(
            functools.partial(run_partpsp, cfg=self.train_cfg,
                              partition=self.partition,
                              loss_fn=self.loss_fn, plan=self.plan,
                              hooks=hooks, mechanism=self.mechanism),
            donate_argnums=(0,)))

    def step_fn(self, t: int = 0):
        """Jitted per-round reference step (the loop driver's primitive)
        with round-``t`` mixing operands bound statically — the classic
        ``partpsp_step`` closure the seed drivers built by hand."""
        self._require_protocol()
        mix = self.plan.mix_at(t)
        return jax.jit(functools.partial(
            partpsp_step, cfg=self.train_cfg, partition=self.partition,
            loss_fn=self.loss_fn, mechanism=self.mechanism, **mix))

    # -- drivers -------------------------------------------------------------

    @property
    def _calls(self) -> int:
        """How many run()/train() calls this session has begun: the ``call``
        id every host span of the latest call carries."""
        return self.__dict__.get("_n_calls", 0)

    def _next_call(self) -> int:
        object.__setattr__(self, "_n_calls", self._calls + 1)
        return self._calls

    @property
    def _protected(self) -> bool:
        return bool(self.cfg is not None and self.cfg.noise
                    and self.cfg.gamma_n > 0)

    def epsilon_spent(self, rounds: int, *, start: int = 0) -> float:
        """Composed epsilon of rounds ``[start, start + rounds)`` (sync
        rounds spend none)."""
        if not self._protected or rounds <= 0:
            return 0.0
        sync = self.cfg.sync_interval
        protected = sum(1 for t in range(start, start + rounds)
                        if not is_sync_round(t, sync))
        return protected * self.cfg.epsilon_per_round

    def _context(self, rounds: int, algorithm: str, d_s: int = 0) -> RunContext:
        return RunContext(cfg=self.cfg, plan=self.plan, n_nodes=self.n_nodes,
                          rounds=rounds, algorithm=algorithm,
                          protected=self._protected, d_s=d_s)

    def _drive(self, segments: Iterator, hooks: Sequence[RoundHook],
               d_s: int, start: int, *, call: int,
               compiles0: int) -> RunReport:
        """Shared host loop: consume hooks per segment, assemble the report.

        A strict hook aborts between segments (any :class:`RunAbort` —
        BudgetExhausted, WatchdogAbort); the report then carries the
        partial run with ``aborted=True``. The report accounts only the
        rounds *this* call executed — resumed runs (``start > 0``) never
        re-count the prefix.

        Wall-clock split: when the call compiled (the process-wide
        compile count grew since ``compiles0``, taken as the call began),
        the first segment is synced and its wall time — tracing + XLA
        compilation of the scan + its execution — is reported as
        ``compile_s``; everything after is steady-state ``run_s``. A call
        that compiled nothing reports ``compile_s`` 0 and makes no sync
        before the trajectory is read back.

        Hooks exposing a ``segment_span`` method (duck-typed — the
        :class:`repro.obs.timeline.TimelineHook` seam) get per-segment
        host timing: with one attached every segment is synced before its
        boundary is stamped, so execute vs hook-consume spans are real
        device time.

        Every wait on the device is a ``repro.api.wait`` span and counts
        in ``RunReport.counts["host_syncs"]``. Each segment's trajectory
        leaves start their copy to the host as the segment is received;
        the report reads them all back in one batched sync
        (``jax.device_get``), and ``RunReport.counts["readback_leaves"]``
        counts the leaves that sync fetched.
        """
        t_start = time.time()
        compile_s = 0.0
        compiled = False
        syncs = 0
        trajs: list[dict[str, Any]] = []
        state = None
        done = start
        aborted = False
        reason = None
        span_hooks = [h for h in hooks if hasattr(h, "segment_span")]
        seg_start = t_start

        def wait(fetch, x):
            nonlocal syncs
            syncs += 1
            with span(SPAN_API_WAIT, call=call):
                return fetch(x)

        try:
            for t0, n, state, traj in segments:
                done = t0 + n
                first = not trajs
                if first:
                    compiled = compile_count() > compiles0
                exec_end = None
                if (first and compiled) or span_hooks:
                    # End of the compiling first segment = compile + first
                    # execution; sync so the boundary is real device time,
                    # not the async dispatch returning early. Span hooks
                    # need the same sync on every segment.
                    wait(jax.block_until_ready, traj)
                    exec_end = time.time()
                    if first and compiled:
                        compile_s = exec_end - t_start
                trajs.append(traj)
                _copy_to_host_async(traj)
                if hooks:
                    with span(SPAN_API_HOOKS, call=call):
                        for h in hooks:
                            h.consume(traj, t0=t0)
                if span_hooks:
                    consume_end = time.time()
                    for h in span_hooks:
                        h.segment_span(t0=t0, n=n, start=seg_start,
                                       execute_end=exec_end,
                                       consume_end=consume_end,
                                       compiled=first and compiled)
                    seg_start = consume_end
        except RunAbort as e:
            aborted = True
            reason = str(e)
        finally:
            if hooks:
                with span(SPAN_API_HOOKS, call=call):
                    for h in hooks:
                        h.finish()
        with span(SPAN_API_REPORT, call=call) as report_span:
            trajectory = {}
            leaves = sum(len(t) for t in trajs)
            if trajs:
                host = wait(jax.device_get, trajs)
                trajectory = {k: np.concatenate([t[k] for t in host])
                              for k in trajs[0]}
            executed = done - start
            # Any hook exposing network_stats() (repro.net.stats.
            # NetworkStatsHook — duck-typed so repro.api never imports
            # repro.net) contributes the realized-network record.
            network = None
            for h in hooks:
                stats_fn = getattr(h, "network_stats", None)
                if stats_fn is not None:
                    network = stats_fn()
            counts = {"dispatches": len(trajs), "host_syncs": syncs,
                      "readback_leaves": leaves,
                      "compiles": compile_count() - compiles0}
            report_span.set_metadata(**counts)
            report = RunReport(
                state=state, trajectory=trajectory, rounds=executed,
                epsilon_spent=self.epsilon_spent(executed, start=start),
                wire_bytes=estimate_wire_bytes(self.plan, self.n_nodes, d_s,
                                               executed),
                compile_s=compile_s,
                run_s=time.time() - t_start - compile_s, aborted=aborted,
                abort_reason=reason, network=network, counts=counts)
        # Run-level publication (run.compile_s / run.run_s gauges, the
        # timeline artifact) — after the report exists, abort included.
        # getattr: duck-typed hooks predating the base class keep working.
        if hooks:
            with span(SPAN_API_HOOKS, call=call):
                for h in hooks:
                    finish_run = getattr(h, "finish_run", None)
                    if finish_run is not None:
                        finish_run(report)
        return report

    def run(
        self,
        rounds: int,
        *,
        values: PyTree | None = None,
        state: DPPSState | None = None,
        eps_at: Callable[[int], PyTree] | None = None,
        hooks: Iterable[RoundHook] = (),
        key: jax.Array | None = None,
        start: int = 0,
    ) -> RunReport:
        """Run ``rounds`` DPPS protocol rounds through the scan engine.

        ``values`` (node-stacked private values) seeds a fresh state;
        ``state`` resumes an existing one. ``eps_at(t)`` supplies the
        per-round perturbation tree (``None`` = pure consensus, zero
        perturbation). Execution is chunked into ``plan.chunk``-round
        compiled segments; hooks consume at every boundary.
        """
        self._require_protocol()
        call = self._next_call()
        with span(SPAN_API_RUN, call=call):
            compiles0 = compile_count()
            if state is None:
                if values is None:
                    raise ValueError("run() needs values= (fresh) or state=")
                with span(SPAN_API_STATE_INIT, call=call):
                    state = self.consensus_state(values)
            with span(SPAN_API_COPY_STATE, call=call):
                state = _own_buffers(state)
            key = self.base_key if key is None else key
            hooks = tuple(hooks)
            d_s = sum(int(np.prod(x.shape[1:])) if x.ndim > 1 else 1
                      for x in jax.tree_util.tree_leaves(state.push.s))
            if hooks:
                with span(SPAN_API_HOOKS, call=call):
                    for h in hooks:
                        h.prepare(self._context(rounds, "dpps", d_s))
            run_chunk = self.consensus_runner(hooks)
            chunk = self.plan.chunk

            def segments():
                st = state
                for t0 in range(start, start + rounds, chunk):
                    n = min(chunk, start + rounds - t0)
                    ids = dict(call=call, t0=t0, rounds=n)
                    if eps_at is None:
                        with span(SPAN_ENGINE_DISPATCH, **ids):
                            st, traj = run_chunk(st, None, key, rounds=n)
                    else:
                        with span(SPAN_ENGINE_INPUTS, **ids):
                            eps = stack_rounds(eps_at, t0, n)
                        with span(SPAN_ENGINE_DISPATCH, **ids):
                            st, traj = run_chunk(st, eps, key)
                    yield t0, n, st, traj

            return self._drive(segments(), hooks, d_s, start, call=call,
                               compiles0=compiles0)

    def train(
        self,
        rounds: int,
        batch_at: Callable[[int], PyTree],
        *,
        state: PartPSPState | None = None,
        hooks: Iterable[RoundHook] = (),
        key: jax.Array | None = None,
        start: int = 0,
        driver: str = "engine",
    ) -> RunReport:
        """Train ``rounds`` PartPSP rounds (Alg. 2).

        ``driver="engine"`` (default) scans ``plan.chunk``-round segments —
        one XLA dispatch each; ``driver="loop"`` is the per-round reference
        path (pytree runtime, one dispatch per round) kept for
        engine-vs-loop comparisons — both fold the absolute round counter
        into the same base key, so trajectories are bit-comparable.
        ``start`` resumes at an absolute round (state carries the counter;
        batches and sync/ledger bookkeeping follow it).
        """
        self._require_protocol()
        if driver not in ("engine", "loop"):
            raise ValueError(f"unknown driver {driver!r}")
        call = self._next_call()
        with span(SPAN_API_TRAIN, call=call):
            compiles0 = compile_count()
            if state is None:
                with span(SPAN_API_STATE_INIT, call=call):
                    state = self.train_state()
            with span(SPAN_API_COPY_STATE, call=call):
                state = _own_buffers(state)
            key = self.base_key if key is None else key
            hooks = tuple(hooks)
            if hooks:
                with span(SPAN_API_HOOKS, call=call):
                    for h in hooks:
                        h.prepare(self._context(rounds, self.algorithm,
                                                self.partition.d_shared()))
            if driver == "engine":
                run_chunk = self.segment_runner(hooks)
                segments = run_segments(run_chunk, state, batch_at, key,
                                        steps=rounds, chunk=self.plan.chunk,
                                        start=start, call=call)
            else:
                segments = self._loop_segments(state, batch_at, key, rounds,
                                               start, hooks, call)
            return self._drive(segments, hooks, self.partition.d_shared(),
                               start, call=call, compiles0=compiles0)

    def _loop_segments(self, state, batch_at, key, rounds, start, hooks,
                       call):
        """Per-round reference driver as a segment stream (T=1 segments).

        Runs the pytree path (no packed layout — the loop is the oracle)
        with per-round mixing operands, so time-varying topologies rotate
        correctly; hook captures run eagerly on the concrete diagnostics.
        """
        spec = hook_trace_spec(hooks)
        codec = getattr(self.plan, "wire", None)
        if codec is not None:
            raise ValueError(
                f"the loop driver runs the pytree path; wire codec "
                f"{codec.name!r} needs the packed buffer — use "
                f"driver='engine'")
        if self.cfg.wire_dtype != "f32":
            raise ValueError("the loop driver runs the pytree path; "
                             "wire_dtype='bf16' needs driver='engine'")
        plan = self.plan
        if plan.schedule == "circulant":
            step = jax.jit(functools.partial(
                partpsp_step, cfg=self.train_cfg, partition=self.partition,
                loss_fn=self.loss_fn, return_s_half=spec.needs_s_half,
                return_wire_stats=spec.needs_wire_stats, tap=spec.tap,
                mechanism=self.mechanism, offsets=plan.offsets))
            mix_for = lambda t: ({"mix_weights":
                                  plan.mix_weights[t % plan.period]}, None)
        elif plan.schedule == "sparse":
            step = jax.jit(functools.partial(
                partpsp_step, cfg=self.train_cfg, partition=self.partition,
                loss_fn=self.loss_fn, return_s_half=spec.needs_s_half,
                return_wire_stats=spec.needs_wire_stats, tap=spec.tap,
                mechanism=self.mechanism))
            if getattr(plan, "dynamic", False):
                # Same fault-key fold as the engine's scan body, on the
                # edge list instead of the dense W (see the dense dynamic
                # branch below).
                want_adj = spec.needs_adjacency

                def mix_for(t):
                    r = t % plan.period
                    vals, net = plan.faults.realize_sparse(
                        plan.sparse_idx[r], plan.sparse_vals[r],
                        plan.faults.fault_key(jax.random.fold_in(key, t)), t,
                        with_adjacency=want_adj)
                    return {"sparse_idx": plan.sparse_idx[r],
                            "sparse_vals": vals}, net
            else:
                mix_for = lambda t: (
                    {"sparse_idx": plan.sparse_idx[t % plan.period],
                     "sparse_vals": plan.sparse_vals[t % plan.period]}, None)
        else:
            step = jax.jit(functools.partial(
                partpsp_step, cfg=self.train_cfg, partition=self.partition,
                loss_fn=self.loss_fn, return_s_half=spec.needs_s_half,
                return_wire_stats=spec.needs_wire_stats, tap=spec.tap,
                mechanism=self.mechanism))
            if getattr(plan, "dynamic", False):
                # Same fault-key fold the engine's scan body uses
                # (FaultModel.fault_key of fold_in(base, t)), so the loop
                # realizes the identical masked W per round and stays
                # bit-comparable to the engine under faults.
                want_adj = spec.needs_adjacency

                def mix_for(t):
                    w, net = plan.faults.realize(
                        plan.ws[t % plan.period],
                        plan.faults.fault_key(jax.random.fold_in(key, t)), t,
                        with_adjacency=want_adj)
                    return {"w": w}, net
            else:
                mix_for = lambda t: ({"w": plan.ws[t % plan.period]}, None)

        if getattr(plan, "delays", None) is not None:
            # Async loop driver: the round's mixing operands (realized by
            # the fault branches above when faults compose) feed the
            # DelayModel's gossip closure instead of the built-in mixing —
            # the same open_round the engine's scan body builds, from the
            # same per-round key fold, so loop and engine trajectories
            # stay bit-identical under delays.
            delays = plan.delays
            needs_ws = spec.needs_wire_stats

            def async_step(state, batch, k, **mix):
                gossip_fn, close = delays.open_round(
                    state.dpps.push, state.dpps.mail, k, state.dpps.t, **mix)
                st2, m = partpsp_step(
                    state, batch, k, cfg=self.train_cfg,
                    partition=self.partition, loss_fn=self.loss_fn,
                    return_s_half=spec.needs_s_half,
                    return_wire_stats=needs_ws, tap=spec.tap,
                    mechanism=self.mechanism, gossip_fn=gossip_fn)
                mail_new, stats = close()
                m = dict(m, **stats)
                if needs_ws:
                    m["wd_mass_drift"] = jnp.abs(
                        stats["async_mass_mean"] - 1.0)
                return st2._replace(
                    dpps=st2.dpps._replace(mail=mail_new)), m

            step = jax.jit(async_step)
            state = state._replace(dpps=self._attach_mail(state.dpps))

        for t in range(start, start + rounds):
            ids = dict(call=call, t0=t, rounds=1)
            with span(SPAN_ENGINE_INPUTS, **ids):
                batch = batch_at(t)
                mix, net = mix_for(t)
            with span(SPAN_ENGINE_DISPATCH, **ids):
                state, m = step(state, batch, jax.random.fold_in(key, t),
                                **mix)
            if net is not None:
                m = dict(m, **net)
            rows = capture_rows(m, hooks)
            yield t, 1, state, jax.tree_util.tree_map(lambda x: x[None], rows)

    # -- profiling -----------------------------------------------------------

    def profile(
        self,
        rounds: int = 50,
        *,
        values: PyTree | None = None,
        state: Any = None,
        batch_at: Callable[[int], PyTree] | None = None,
        hooks: Iterable[RoundHook] = (),
        key: jax.Array | None = None,
        trace_dir: str | None = None,
    ):
        """Profile one compiled segment: wall-clock split + phase breakdown.

        Compiles and runs a single ``min(rounds, plan.chunk)``-round
        segment of the consensus protocol (``values=``/``state=``) or of
        PartPSP training (``batch_at=``), timing trace, compile, and
        execute separately, and captures a ``jax.profiler`` device trace
        of the execute. The trace's per-op times are joined against the
        compiled module's ``op_name`` metadata — where the
        :func:`repro.obs.phase` annotations survive — into a per-phase
        device-time breakdown (:class:`repro.obs.ProfileReport`), the
        trace read with ``jax.profiler.ProfileData``. When the trace holds
        no op execution the breakdown degrades to empty with a ``note``;
        the wall-clock split always works.

        ``hooks`` are attached trace-time only (their captures shape the
        profiled program exactly as in :meth:`run`/:meth:`train`); their
        host-side ``consume`` does not run. ``trace_dir`` keeps the raw
        profiler trace on disk (e.g. for TensorBoard); by default it lives
        in a temp dir deleted after the join. The profiled call does NOT
        donate its inputs, so the passed state survives.
        """
        import shutil
        import tempfile

        from repro.obs.trace import ProfileReport, phase_breakdown

        self._require_protocol()
        key = self.base_key if key is None else key
        hooks = tuple(hooks)
        n = min(rounds, self.plan.chunk)
        if batch_at is not None:
            if state is None:
                state = self.train_state()
            fn = functools.partial(
                run_partpsp, cfg=self.train_cfg, partition=self.partition,
                loss_fn=self.loss_fn, plan=self.plan, hooks=hooks,
                mechanism=self.mechanism)
            args = (state, stack_rounds(batch_at, 0, n), key)
        else:
            if state is None:
                if values is None:
                    raise ValueError("profile() needs values=/state= "
                                     "(consensus) or batch_at= (training)")
                state = self.consensus_state(values)
            fn = functools.partial(run_dpps, cfg=self.cfg, plan=self.plan,
                                   hooks=hooks, mechanism=self.mechanism,
                                   rounds=n)
            args = (state, None, key)

        t0 = time.time()
        lowered = jax.jit(fn).lower(*args)
        trace_s = time.time() - t0
        t0 = time.time()
        compiled = lowered.compile()
        compile_s = time.time() - t0
        hlo = compiled.as_text()

        out_dir = trace_dir if trace_dir is not None else tempfile.mkdtemp(
            prefix="repro-obs-profile-")
        try:
            t0 = time.time()
            with jax.profiler.trace(out_dir):
                out = compiled(*args)
                jax.block_until_ready(out)
            execute_s = time.time() - t0
            phases, device_total_s, note = phase_breakdown(hlo, out_dir)
        finally:
            if trace_dir is None:
                shutil.rmtree(out_dir, ignore_errors=True)
        return ProfileReport(
            rounds=n, backend=jax.default_backend(), trace_s=trace_s,
            compile_s=compile_s, execute_s=execute_s, phases=phases,
            device_total_s=device_total_s, trace_dir=trace_dir, note=note)

    # -- cross-run registry --------------------------------------------------

    def _fingerprint(self) -> str:
        """Stable hash of the session's config/plan scalars — the
        registry's comparability stamp for session records (two runs
        with the same fingerprint + scale are the same deployment)."""
        import hashlib
        import json

        plan, cfg = self.plan, self.cfg
        desc = {
            "algorithm": self.algorithm,
            "n_nodes": self.n_nodes,
            "schedule": getattr(plan, "schedule", None),
            "packed": getattr(plan, "packed", None),
            "wire_dtype": getattr(plan, "wire_dtype", None),
            "chunk": getattr(plan, "chunk", None),
            "period": getattr(plan, "period", None),
            "sync_interval": getattr(cfg, "sync_interval", None),
            "b": getattr(cfg, "b", None),
            "gamma_n": getattr(cfg, "gamma_n", None),
            "noise": getattr(cfg, "noise", None),
            "faults": repr(getattr(plan, "faults", None)),
            "delays": repr(getattr(plan, "delays", None)),
            "wire": repr(getattr(plan, "wire", None)),
        }
        blob = json.dumps(desc, sort_keys=True, default=str).encode()
        return hashlib.sha256(blob).hexdigest()[:16]

    def record(self, report: RunReport, *, name: str,
               history: str = "BENCH_history.jsonl",
               extra: dict[str, float] | None = None):
        """Append this run to the cross-run registry (lazy import — the
        obs layer stays optional for sessions that never record).

        The record lands as bench ``session/<name>`` with the session's
        scale dict (n_nodes, d_s, rounds, schedule, packed, backend) and
        fingerprint; ``python -m repro.obs.registry check`` then gates
        later runs of the same deployment against this one (us/round,
        wire bytes, epsilon). ``extra`` adds caller metrics (e.g. a
        final consensus error). Returns the appended
        :class:`repro.obs.registry.RunRecord`.
        """
        from repro.obs.registry import RunRecord, append_record

        self._require_protocol()
        push = getattr(report.state, "push", None)
        if push is None and report.state is not None:
            push = getattr(getattr(report.state, "dpps", None), "push", None)
        d_s = 0
        if push is not None:
            d_s = sum(int(np.prod(x.shape[1:])) if x.ndim > 1 else 1
                      for x in jax.tree_util.tree_leaves(push.s))
        # run_s leaves out the first segment only when the call compiled
        chunk = (getattr(self.plan, "chunk", 0) or 0) if report.compile_s \
            else 0
        steady = max(report.rounds - chunk, 0)
        scale = {
            "n_nodes": self.n_nodes, "d_s": d_s,
            "rounds": report.rounds,
            "schedule": getattr(self.plan, "schedule", None),
            "packed": getattr(self.plan, "packed", None),
            "backend": jax.default_backend(),
            "algorithm": self.algorithm,
        }
        rec = RunRecord.from_report(
            name, report, scale=scale, fingerprint=self._fingerprint(),
            backend=jax.default_backend(), steady_rounds=steady,
            extra=extra)
        append_record(rec, history)
        return rec

    # -- serving -------------------------------------------------------------

    @staticmethod
    def _graft_cache(dst, src):
        """Copy a prompt-sized cache prefix into a full-capacity cache."""
        if dst.ndim >= 3 and src.ndim == dst.ndim and dst.shape != src.shape:
            idx = tuple(slice(0, d) for d in src.shape)
            return dst.at[idx].set(src.astype(dst.dtype))
        return src.astype(dst.dtype)

    def serve(
        self,
        params: PyTree,
        batch: dict[str, Any],
        *,
        gen: int,
        temperature: float = 1.0,
        key: jax.Array | None = None,
        enc: Any = None,
        step_inputs: Any = None,
    ) -> ServeReport:
        """Batched prefill + scan-compiled decode on ``params``.

        Owns the serving plumbing every driver used to hand-roll: jitted
        prefill, rebuilding the KV/SSM cache at prompt+gen capacity with
        the prompt prefix grafted in, and the one-dispatch
        ``repro.engine.run_decode`` generation. ``enc`` is the VLM image
        encoding; embedding-input models must pass precomputed
        ``step_inputs`` of shape (gen-1, B, d_model).
        """
        model = self.model
        if model is None or not hasattr(model, "prefill"):
            raise ValueError("serve() needs a servable model= at build time "
                             "(prefill/init_cache/decode_step)")
        key = self.base_key if key is None else key
        ref = batch.get("tokens", batch.get("labels"))
        b, prompt_len = ref.shape[0], ref.shape[1]

        t0 = time.time()
        logits, cache = jax.jit(model.prefill)(params, batch)
        full = model.init_cache(b, prompt_len + gen)
        cache = jax.tree_util.tree_map(self._graft_cache, full, cache)
        jax.block_until_ready(logits)
        prefill_s = time.time() - t0

        tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        steps = gen - 1
        cfg = getattr(model, "cfg", None)
        if (cfg is not None and getattr(cfg, "input_mode", None) ==
                "embeddings" and steps > 0 and step_inputs is None):
            raise ValueError("embedding-input models need step_inputs= "
                             "of shape (gen-1, B, d_model)")

        def run_fn(params, cache, tok0, k, enc, step_inputs):
            # params/enc are traced arguments so the compiled scan does
            # not bake the weights in as XLA constants
            def decode_fn(c, step_in, pos):
                return model.decode_step(params, c, step_in, pos, enc)

            return run_decode(decode_fn, cache, tok0, k,
                              start_pos=prompt_len, steps=steps,
                              temperature=temperature,
                              step_inputs=step_inputs)

        t0 = time.time()
        if steps > 0:
            toks, cache = jax.jit(run_fn)(params, cache, tok, key, enc,
                                          step_inputs)
            tokens = jnp.concatenate([tok[:, None], toks.T], axis=1)
        else:
            tokens = tok[:, None]
        jax.block_until_ready(tokens)
        return ServeReport(tokens=tokens, prefill_s=prefill_s,
                           decode_s=time.time() - t0, steps=steps)

    # -- checkpoint / resume -------------------------------------------------

    def save(self, path: str, state: Any, *, step: int = 0,
             metadata: dict | None = None) -> None:
        """Persist a full protocol/training state (resume payload)."""
        save_checkpoint(path, state, step=step, metadata=metadata)

    def restore(self, path: str, template: Any = None) -> tuple[Any, dict]:
        """Restore a state saved with :meth:`save`; resumes the exact
        noise stream (the state carries the absolute round counter the
        engine folds into the base key)."""
        if template is None:
            template = self.train_state()
        return load_checkpoint(path, template)

    def save_consensus(self, path: str, state: PartPSPState, *,
                       step: int = 0, metadata: dict | None = None) -> None:
        """Persist the protocol *output* for serving: s-bar + node 0's
        local params (identical across nodes for the shared part)."""
        save_checkpoint(path, self.consensus_view(state, 0), step=step,
                        metadata=metadata)


Session = ProtocolSession
