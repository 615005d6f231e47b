"""Scan-compiled multi-round protocol drivers.

The seed repo dispatched ``dpps_step`` / ``partpsp_step`` from a Python loop
— one XLA dispatch (plus host-side key folding) per round, which dominates
the per-round cost at protocol scale. These drivers wrap the round in
``jax.lax.scan`` so an entire training segment compiles and dispatches once:

* :func:`run_dpps`     — T rounds of the raw DPPS protocol (Alg. 1).
* :func:`run_partpsp`  — T rounds of PartPSP training (Alg. 2); the batch
  stream is a stacked pytree with a leading round axis.
* :func:`run_decode`   — scan-compiled autoregressive decode for serving.
* :func:`stack_rounds` — host helper stacking per-round pytrees into the
  ``(T, ...)`` layout the scans consume.

Trajectory capture is chunked: each driver captures per-round metrics as
scan outputs, and callers split long runs into ``ProtocolPlan.chunk``-sized
segments so metrics stay bounded and checkpoints land on segment boundaries
(see ``launch/train.py``).

Packed carry: with ``plan.packed`` (the default) the drivers flatten the
shared tree into one contiguous ``(N, d_pad)`` buffer
(:class:`repro.core.packing.PackedLayout`) *before* the scan and unpack it
*after* — the scan carry is a single fused buffer instead of a many-leaf
tree, and every per-round pass (perturb, noise, norms, dense mix) runs
once over it. Callers' view is unchanged: states in and out are ordinary
pytree states, so checkpoints, metrics and the loop driver interoperate
bit-for-bit (f32 wire mode is pinned bit-identical to the pytree path in
tests/test_engine.py). Jit the drivers with ``donate_argnums=(0,)`` so XLA
aliases the packed carry in place — the per-round Python loop holds two
copies of the full shared tree per step; the donated packed scan holds
one.

PRNG discipline: drivers receive one *base* key and fold the absolute round
counter carried in the protocol state into it each round —
``fold_in(base_key, state.t)``. A Python loop calling the per-round step
with ``fold_in(base_key, t)`` therefore produces bit-identical trajectories
(tests/test_engine.py pins this for both schedules), and resuming from a
checkpointed state continues the exact same noise stream.

The private ``_gossip_builder`` / ``_node_ops`` / ``_key_fold`` hooks are
the seam ``repro.engine.shard`` uses to run the identical scan under
``shard_map`` with mesh-collective gossip.

Fault injection (``ProtocolPlan.dynamic``, selected by an active
``repro.net.faults.FaultModel``): the scan body realizes each round's
masked, column-renormalized W from the nominal one before the step and
merges the realized-network diagnostics (out-degrees, dropped edges,
adjacency) into the trajectory. Inactive/absent fault models emit no
masking code — the traced program is the plain engine's (the golden HLO
pins in tests/test_api.py stay binding).

Bounded-delay async (``ProtocolPlan.delays``, an active
``repro.net.delays.DelayModel``): the scan carry gains a message
``Mailbox`` (``DPPSState.mail``; packed alongside the state), each round's
mixing runs through ``DelayModel.open_round`` as a ``gossip_fn`` over the
realized weights (faults compose — masking happens first), and the
per-round staleness/timeout/participation stats join the trajectory.
Inactive/absent delay models are dropped at plan build, so the delay-0
program is bit-identical to the synchronous engine (pinned in
tests/test_async.py).

Wire compression (``ProtocolPlan.wire``, an active
``repro.wire.WireCodec``): the round encodes the noised wire inside the
step (noise-then-compress); the only engine-level work is carrying the
error-feedback residual for stateful codecs (``DPPSState.resid``,
attached here like the mailbox) and forcing single-leaf trees onto the
packed layout. Inactive/identity codecs are dropped at plan build —
the compiled program stays the raw packed engine's.
"""
from __future__ import annotations

import warnings
from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp

from repro.core.dpps import DPPSConfig, DPPSState, dpps_step
from repro.core.packing import PackedLayout
from repro.core.partpsp import PartPSPConfig, PartPSPState, partpsp_step
from repro.core.pushsum import PushSumState
from repro.core.tree_utils import PyTree
from repro.engine.plan import ProtocolPlan
from repro.obs.trace import (
    PHASE_FAULTS,
    PHASE_PACK,
    PHASE_UNPACK,
    SPAN_ENGINE_DISPATCH,
    SPAN_ENGINE_INPUTS,
    phase,
    span,
)

__all__ = ["run_dpps", "run_partpsp", "run_decode", "run_segments",
           "stack_rounds", "wire_layout"]

# Deprecation keys already warned about this process (the adapters warn
# exactly once per kwarg, not once per call — tests/test_api.py pins this).
_WARNED: set[str] = set()


def _warn_once(key: str, message: str) -> None:
    if key in _WARNED:
        return
    _WARNED.add(key)
    warnings.warn(message, DeprecationWarning, stacklevel=3)


def _resolve_hooks(hooks: Sequence[Any], tap, track_real: bool, caller: str):
    """Hook pipeline + deprecated kwarg adapters -> (hooks, TraceSpec).

    ``tap=`` and ``track_real=`` predate the hook pipeline (PR 2); they now
    adapt into the equivalent first-class hooks (repro.api.hooks) so the
    traced program — and therefore every pinned trajectory — is unchanged,
    and warn once per process. New code passes ``hooks=`` directly.
    """
    hooks = tuple(hooks)
    if tap is not None:
        from repro.api.hooks import TranscriptHook

        _warn_once(f"{caller}:tap",
                   f"{caller}(tap=...) is deprecated; pass "
                   "hooks=[repro.api.TranscriptHook(tap)] instead")
        hooks += (TranscriptHook(tap),)
    if track_real:
        from repro.api.hooks import RealSensitivityHook

        _warn_once(f"{caller}:track_real",
                   f"{caller}(track_real=True) is deprecated; pass "
                   "hooks=[repro.api.RealSensitivityHook()] instead")
        hooks += (RealSensitivityHook(),)
    from repro.api.hooks import hook_trace_spec

    return hooks, hook_trace_spec(hooks)


def stack_rounds(make_round: Callable[[int], PyTree], t0: int, n: int) -> PyTree:
    """Stack host-produced per-round pytrees into leading-(T,) scan inputs."""
    items = [make_round(t) for t in range(t0, t0 + n)]
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *items)


def run_segments(run_chunk: Callable, state, batch_at: Callable[[int], PyTree],
                 key: jax.Array, *, steps: int, chunk: int, start: int = 0,
                 call: int = 0):
    """Drive a jitted segment runner over ``steps`` rounds in ``chunk``s.

    Yields ``(t0, n, state, traj)`` after each segment: the segment's first
    absolute round, its length (the final segment may be shorter), the
    advanced state, and the per-round metric trajectory. Host work (batch
    stacking via ``batch_at``) happens between dispatches, and checkpoints
    naturally land on segment boundaries. Each segment's input stacking
    and dispatch are ``repro.engine.inputs`` / ``repro.engine.dispatch``
    host spans, stamped with ``call`` (the caller's call id), ``t0`` and
    ``rounds``.
    """
    for t0 in range(start, start + steps, chunk):
        n = min(chunk, start + steps - t0)
        with span(SPAN_ENGINE_INPUTS, call=call, t0=t0, rounds=n):
            batches = stack_rounds(batch_at, t0, n)
        with span(SPAN_ENGINE_DISPATCH, call=call, t0=t0, rounds=n):
            state, traj = run_chunk(state, batches, key)
        yield t0, n, state, traj


def _round_kwargs(plan: ProtocolPlan, t, gossip_builder, node_ops):
    """Mixing/reduction kwargs for the round at (possibly traced) index t."""
    mix = plan.mix_at(t)
    kwargs: dict[str, Any] = {}
    if gossip_builder is not None:
        kwargs["gossip_fn"] = gossip_builder(mix)
    else:
        kwargs.update(mix)
    if node_ops is not None:
        kwargs["node_ops"] = node_ops
    return kwargs


def _check_dynamic(plan: ProtocolPlan, gossip_builder) -> bool:
    """Whether this run masks W in-scan (and that the mode is supported)."""
    if not getattr(plan, "dynamic", False):
        return False
    if gossip_builder is not None:
        raise NotImplementedError(
            "fault injection (ProtocolPlan.dynamic) is not implemented for "
            "the sharded engine's collective gossip — static plans shard "
            "(including schedule='sparse'), fault-masked ones do not; run "
            "the fault study on the single-device engine (schedule='sparse' "
            "masks the edge list without stacking dense (T, N, N) weights), "
            "or detach the FaultModel on the mesh")
    return True


def _check_async(plan: ProtocolPlan, gossip_builder, cfg: DPPSConfig) -> bool:
    """Whether this run carries a message mailbox (ProtocolPlan.delays).

    ``cfg`` must already be plan-resolved — the sync-interval check reads
    the stamped value. The sharded engine's collective gossip and the bf16
    wire are rejected here: the mailbox carry accumulates in f32 and the
    delay draws need the explicit weight form on one device.
    """
    delays = getattr(plan, "delays", None)
    if delays is None:
        return False
    if gossip_builder is not None:
        raise NotImplementedError(
            "bounded-delay async gossip (ProtocolPlan.delays) is not "
            "implemented for the sharded engine's collective gossip; run "
            "the async study on the single-device engine, or detach the "
            "DelayModel on the mesh")
    if cfg.wire_dtype != "f32":
        codec = getattr(plan, "wire", None)
        what = (f"wire codec {codec.name!r}" if codec is not None
                else "bf16 wire (wire_dtype='bf16')")
        raise NotImplementedError(
            f"{what} does not compose with the async mailbox runtime: the "
            "mailbox calendars accumulate in-flight mass in f32. Value "
            "codecs (int8, topk:K) DO compose — they encode the payload "
            "before it is enqueued and the calendars stay f32 — so use "
            "one of those, or drop to the raw f32 wire")
    if cfg.sync_interval > 0:
        raise ValueError(
            "sync_interval > 0 with an active DelayModel would average "
            "node states while message mass is still in flight (breaking "
            "conservation); use sync_interval=0")
    return True


def _open_async(plan: ProtocolPlan, kwargs: dict[str, Any],
                push: PushSumState, mail, round_key: jax.Array, t):
    """Swap the round's mixing operands for the DelayModel's gossip closure.

    Runs *after* ``_realize_faults`` so the mailbox consumes the realized
    (masked, renormalized) weights. Returns the ``close`` callback the body
    calls after the step for ``(new_mailbox, stats)``.
    """
    mix = {name: kwargs.pop(name)
           for name in ("w", "sparse_idx", "sparse_vals") if name in kwargs}
    gossip_fn, close = plan.delays.open_round(push, mail, round_key, t, **mix)
    kwargs["gossip_fn"] = gossip_fn
    return close


def _async_merge(st2: DPPSState, diag: dict[str, Any], close,
                 needs_wire_stats: bool) -> DPPSState:
    """Fold the round's mailbox + async stats back into state/diagnostics."""
    mail_new, stats = close()
    diag.update(stats)
    if needs_wire_stats:
        # dpps_step's drift only sees the state's a-mass; under async the
        # invariant is state + inbox + calendar mass (async_mass_mean).
        diag["wd_mass_drift"] = jnp.abs(stats["async_mass_mean"] - 1.0)
    return st2._replace(mail=mail_new)


def _realize_faults(plan: ProtocolPlan, kwargs: dict[str, Any],
                    round_key: jax.Array, t,
                    with_adjacency: bool) -> dict[str, Any]:
    """Dynamic plans: replace the nominal W with the round's realized one.

    The fault mask is drawn from ``FaultModel.fault_key(round_key)`` — a
    salted fold of the same per-round key the noise draw consumes, so the
    mask stream is independent of the noise stream, identical between the
    scan engine and the loop driver, and host-re-derivable from the base
    key. Returns the round's network diagnostics (realized out-degrees,
    dropped edges; the (N, N) realized adjacency only when a hook declared
    ``needs_adjacency``) for the trajectory/ledger. Sparse plans mask and
    renormalize the round's edge-list weights in place
    (``FaultModel.realize_sparse``) — the dense W never exists.
    """
    with phase(PHASE_FAULTS):
        if "sparse_idx" in kwargs:
            vals_real, net = plan.faults.realize_sparse(
                kwargs["sparse_idx"], kwargs["sparse_vals"],
                plan.faults.fault_key(round_key), t,
                with_adjacency=with_adjacency)
            kwargs["sparse_vals"] = vals_real
            return net
        w_real, net = plan.faults.realize(
            kwargs["w"], plan.faults.fault_key(round_key), t,
            with_adjacency=with_adjacency)
        kwargs["w"] = w_real
        return net


def _capture(diag: dict[str, Any], hooks: Sequence[Any]) -> dict[str, Any]:
    """Round diagnostics -> scan outputs (repro.api.hooks.capture_rows —
    imported lazily: repro.api imports this module at package init)."""
    from repro.api.hooks import capture_rows

    return capture_rows(diag, hooks)


def wire_layout(plan: ProtocolPlan, shared: PyTree) -> PackedLayout | None:
    """The packed layout the drivers will run ``shared`` under (or None
    for the pytree path). Callers pre-packing inputs into wire layout
    (e.g. an eps_seq buffer for :func:`run_dpps`) must pack with THIS
    layout — it is None when packed=False, when nothing is shared, or
    when the shared tree is already a single contiguous 2-D leaf (packing
    one leaf removes no per-leaf work, it only adds wire-row copies —
    measured ~1.6x slower at the table4 single-leaf scale; single-leaf
    trees still pack when the plan needs the buffer form: bf16 wire, an
    active wire codec, or the fused Pallas kernels)."""
    leaves = jax.tree_util.tree_leaves(shared)
    if not plan.packed or not leaves:
        return None
    if (len(leaves) == 1 and leaves[0].ndim == 2
            and plan.wire_dtype == "f32" and not plan.use_kernels
            and getattr(plan, "wire", None) is None):
        return None
    # The 128-lane padding exists for the Pallas kernels' tile alignment;
    # the jnp path gains nothing from it and would pay a pad slice+concat
    # per round, so the buffer stays at the exact wire width there (the
    # kernel wrappers also pad internally — the aligned carry just avoids
    # the copy on TPU).
    from repro.core.packing import LANE

    layout = PackedLayout.from_tree(shared,
                                    lane=LANE if plan.use_kernels else 1)
    codec = getattr(plan, "wire", None)
    if codec is not None and getattr(codec, "active", False):
        # Fail fast on codec/width contract violations (e.g. top-k's
        # uint16 index bound) before any compile work happens.
        codec.payload_bytes(layout.d_s)
    return layout


def _pack_dpps(state: DPPSState, layout: PackedLayout) -> DPPSState:
    with phase(PHASE_PACK):
        mail = state.mail
        if mail:
            # Mailbox leaves mirror the state's runtime form: the calendar
            # (B, N, ...) and inbox (N, ...) pack onto the same wire rows
            # (PackedLayout.pack handles arbitrary leading prefixes).
            mail = mail._replace(cal_s=layout.pack(mail.cal_s),
                                 inbox_s=layout.pack(mail.inbox_s))
        return state._replace(push=PushSumState(s=layout.pack(state.push.s),
                                                a=state.push.a),
                              mail=mail)


def _unpack_dpps(state: DPPSState, layout: PackedLayout) -> DPPSState:
    with phase(PHASE_UNPACK):
        mail = state.mail
        if mail:
            mail = mail._replace(cal_s=layout.unpack(mail.cal_s),
                                 inbox_s=layout.unpack(mail.inbox_s))
        return state._replace(
            push=PushSumState(s=layout.unpack(state.push.s),
                              a=state.push.a),
            mail=mail)


def _ensure_mail(state: DPPSState, plan: ProtocolPlan,
                 asynchronous: bool) -> DPPSState:
    """Attach an empty mailbox for async runs; reject orphaned ones.

    Called after packing, so the mailbox mirrors the state's runtime form.
    A state already carrying a mailbox (a resumed async run) keeps it —
    its in-flight mass continues draining on the exact same schedule.
    """
    if asynchronous:
        if not state.mail:
            state = state._replace(mail=plan.delays.init_mailbox(state.push.s))
        return state
    if state.mail:
        raise ValueError(
            "state carries an async Mailbox but the plan has no active "
            "DelayModel — running it synchronously would abandon the "
            "in-flight message mass; keep the DelayModel on the plan (or "
            "drain the mailbox by finishing the async run first)")
    return state


def _ensure_resid(state: DPPSState, plan: ProtocolPlan,
                  layout: PackedLayout | None) -> DPPSState:
    """Attach the error-feedback residual for stateful wire codecs;
    reject orphaned ones (the ``_ensure_mail`` contract).

    A state already carrying a residual (a resumed top-k run) keeps it —
    the un-sent compression error continues to be re-injected.
    """
    codec = getattr(plan, "wire", None)
    if codec is not None and getattr(codec, "stateful", False):
        if layout is None:
            raise ValueError(
                f"wire codec {codec.name!r} needs the packed layout; "
                "build the plan with packed=True")
        if not isinstance(state.resid, jnp.ndarray):
            n = state.push.a.shape[0]
            state = state._replace(
                resid=jnp.zeros((n, layout.d_s), jnp.float32))
        return state
    if isinstance(state.resid, jnp.ndarray):
        raise ValueError(
            "state carries an error-feedback residual but the plan's wire "
            "codec is not stateful — running it would silently drop the "
            "carried compression error; keep the top-k codec on the plan, "
            "or discard the residual explicitly with "
            "state._replace(resid=())")
    return state


def run_dpps(
    state: DPPSState,
    eps_seq: PyTree | None,
    key: jax.Array,
    *,
    cfg: DPPSConfig,
    plan: ProtocolPlan,
    rounds: int | None = None,
    hooks: Sequence[Any] = (),
    track_real: bool = False,
    tap=None,
    mechanism=None,
    _gossip_builder=None,
    _node_ops=None,
    _key_fold=None,
) -> tuple[DPPSState, dict[str, jnp.ndarray]]:
    """Scan ``rounds`` DPPS rounds in one compiled program.

    ``eps_seq``: per-round perturbations, leaves shaped (T, N, ...) — or
    ``None`` for pure consensus (zero perturbation, ``rounds`` required).
    Returns the final state and the per-round diagnostic trajectory (leaves
    (T,) / (T, N)).

    ``hooks`` (:class:`repro.api.hooks.RoundHook` pipeline) is how
    observers attach: each hook's trace-time needs (transcript tap,
    ``s_half``) are threaded into the round and its ``capture`` output is
    stacked into extra trajectory leaves. With ``hooks=()`` the compiled
    program is bit-identical to the hook-free engine (HLO pinned in
    tests/test_api.py); host-side ``consume`` is the caller's job — the
    session front door (:mod:`repro.api.session`) drives it per segment.

    ``tap=`` / ``track_real=`` are deprecated adapters over the equivalent
    hooks (TranscriptHook / RealSensitivityHook) — identical traced
    program, DeprecationWarning once per process. ``mechanism`` swaps the
    Laplace draw for a pluggable
    :class:`repro.audit.mechanisms.NoiseMechanism`; it changes the traced
    program (not an observer), so it stays a first-class kwarg.
    """
    hooks, spec = _resolve_hooks(hooks, tap, track_real, "run_dpps")
    dynamic = _check_dynamic(plan, _gossip_builder)
    want_adj = dynamic and spec.needs_adjacency
    cfg = plan.resolve_dpps(cfg)
    asynchronous = _check_async(plan, _gossip_builder, cfg)
    layout = wire_layout(plan, state.push.s)
    if layout is not None:
        state = _pack_dpps(state, layout)
    state = _ensure_mail(state, plan, asynchronous)
    state = _ensure_resid(state, plan, layout)
    if eps_seq is None:
        if rounds is None:
            raise ValueError("rounds= is required when eps_seq is None")
        zeros = (jnp.zeros_like(state.push.s) if layout is not None
                 else jax.tree_util.tree_map(jnp.zeros_like, state.push.s))
        xs: Any = jnp.arange(rounds)
        eps_at = lambda x: zeros
    else:
        # A pytree eps_seq stays a pytree even when packed: each round's
        # leaf slices go through the layout's per-region perturb add
        # (PackedLayout.add_wire) — same element traffic as the buffer
        # add, no pre-copy of the whole segment into wire layout. Callers
        # that already hold the perturbations in wire layout pass one
        # (T, N, d_pad) buffer instead and the round consumes it directly.
        if layout is not None and isinstance(eps_seq, jnp.ndarray):
            if eps_seq.shape[-1] != layout.d_pad:
                raise ValueError(
                    f"pre-packed eps_seq last dim {eps_seq.shape[-1]} != "
                    f"layout d_pad {layout.d_pad}")
        xs = eps_seq
        eps_at = lambda x: x

    def body(st: DPPSState, x):
        k = jax.random.fold_in(key, st.t)
        if _key_fold is not None:
            k = _key_fold(k)
        kwargs = _round_kwargs(plan, st.t, _gossip_builder, _node_ops)
        net = (_realize_faults(plan, kwargs, k, st.t, want_adj)
               if dynamic else None)
        close = (_open_async(plan, kwargs, st.push, st.mail, k, st.t)
                 if asynchronous else None)
        st2, diag = dpps_step(st, eps_at(x), k, cfg,
                              return_s_half=spec.needs_s_half,
                              return_wire_stats=spec.needs_wire_stats,
                              mechanism=mechanism, tap=spec.tap,
                              layout=layout, **kwargs)
        if close is not None:
            st2 = _async_merge(st2, diag, close, spec.needs_wire_stats)
        if net is not None:
            diag.update(net)
        return st2, _capture(diag, hooks)

    final, traj = jax.lax.scan(body, state, xs)
    if layout is not None:
        final = _unpack_dpps(final, layout)
    return final, traj


def run_partpsp(
    state: PartPSPState,
    batches: PyTree,
    key: jax.Array,
    *,
    cfg: PartPSPConfig,
    partition,
    loss_fn,
    plan: ProtocolPlan,
    hooks: Sequence[Any] = (),
    track_real: bool = False,
    tap=None,
    mechanism=None,
    _gossip_builder=None,
    _node_ops=None,
    _key_fold=None,
) -> tuple[PartPSPState, dict[str, jnp.ndarray]]:
    """Scan one segment of PartPSP training (Alg. 2) in one compiled program.

    ``batches``: stacked round batches, leaves (T, N, per_node, ...) — use
    :func:`stack_rounds` to build them from a host loader. Metrics are
    captured every round; the returned trajectory has (T,)-leading leaves.
    ``hooks`` is the RoundHook pipeline and ``tap=`` / ``track_real=`` its
    deprecated adapters (see :func:`run_dpps`); ``mechanism`` swaps the
    noise draw. All are zero-cost at their defaults.
    """
    hooks, spec = _resolve_hooks(hooks, tap, track_real, "run_partpsp")
    dynamic = _check_dynamic(plan, _gossip_builder)
    want_adj = dynamic and spec.needs_adjacency
    cfg = plan.resolve_partpsp(cfg)
    asynchronous = _check_async(plan, _gossip_builder, cfg.dpps)
    layout = wire_layout(plan, state.dpps.push.s)
    if layout is not None:
        state = state._replace(dpps=_pack_dpps(state.dpps, layout))
    state = state._replace(dpps=_ensure_mail(state.dpps, plan, asynchronous))
    state = state._replace(dpps=_ensure_resid(state.dpps, plan, layout))

    def body(st: PartPSPState, batch_t):
        k = jax.random.fold_in(key, st.dpps.t)
        if _key_fold is not None:
            k = _key_fold(k)
        kwargs = _round_kwargs(plan, st.dpps.t, _gossip_builder, _node_ops)
        net = (_realize_faults(plan, kwargs, k, st.dpps.t, want_adj)
               if dynamic else None)
        close = (_open_async(plan, kwargs, st.dpps.push, st.dpps.mail,
                             k, st.dpps.t)
                 if asynchronous else None)
        st2, m = partpsp_step(st, batch_t, k, cfg=cfg, partition=partition,
                              loss_fn=loss_fn,
                              return_s_half=spec.needs_s_half,
                              return_wire_stats=spec.needs_wire_stats,
                              mechanism=mechanism, tap=spec.tap,
                              layout=layout, **kwargs)
        if close is not None:
            st2 = st2._replace(
                dpps=_async_merge(st2.dpps, m, close, spec.needs_wire_stats))
        if net is not None:
            m.update(net)
        return st2, _capture(m, hooks)

    final, traj = jax.lax.scan(body, state, batches)
    if layout is not None:
        final = final._replace(dpps=_unpack_dpps(final.dpps, layout))
    return final, traj


def run_decode(
    decode_fn: Callable,
    cache: PyTree,
    tok0: jnp.ndarray,
    key: jax.Array,
    *,
    start_pos: int,
    steps: int,
    temperature: float = 1.0,
    step_inputs: jnp.ndarray | None = None,
) -> tuple[jnp.ndarray, PyTree]:
    """Scan-compiled autoregressive decode (serving hot loop).

    ``decode_fn(cache, step_in, pos) -> (logits, new_cache)``. For token
    models the sampled token feeds back as the next ``step_in``; embedding
    models pass precomputed ``step_inputs`` of shape (steps, B, d_model).
    Returns ((steps, B) sampled tokens, final cache).
    """
    positions = start_pos + jnp.arange(steps, dtype=jnp.int32)

    def sample(logits, k):
        k, sub = jax.random.split(k)
        tok = jax.random.categorical(sub, logits / temperature, axis=-1)
        return tok.astype(jnp.int32), k

    if step_inputs is None:
        def body(carry, pos):
            tok, cache, k = carry
            logits, cache = decode_fn(cache, tok, pos)
            tok, k = sample(logits, k)
            return (tok, cache, k), tok
        xs: Any = positions
    else:
        def body(carry, x):
            tok, cache, k = carry
            pos, step_in = x
            logits, cache = decode_fn(cache, step_in, pos)
            tok, k = sample(logits, k)
            return (tok, cache, k), tok
        xs = (positions, step_inputs)

    (_, cache, _), toks = jax.lax.scan(body, (tok0, cache, key), xs)
    return toks, cache
