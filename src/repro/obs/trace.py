"""Phase-scoped tracing: named scopes on the round phases, host spans
around the work the host does between them, and profiling.

:func:`phase` is the annotation the protocol and model code wraps its
device phases in — a thin veneer over ``jax.named_scope`` that also
registers the phase name in :data:`KNOWN_PHASES`. Named scopes change only
HLO *metadata* (``op_name="jit(f)/.../<phase>/<op>"``): the traced ops are
identical, so annotating the hot path is free by construction.

:func:`span` is its host-side twin: a ``jax.profiler.TraceAnnotation``
named ``repro.<layer>.<what>`` (registered in :data:`HOST_SPANS`) that the
api and engine layers open around host work — state build, input
stacking, each segment dispatch, every wait on the device, hook work. The
profiler records these spans in the same trace and on the same clock as
the device ops, so every idle gap of the chip can be named by the host
work over it. With no profiler running a span costs about a microsecond;
keyword ids (``call=``, ``t0=``) become the event's stats.
:func:`compile_count` is the process-wide count of lowerings and backend
compiles (one ``jax.monitoring`` listener), which ``RunReport.counts``
reads per call.

The profiling half turns one compiled segment into a
:class:`ProfileReport`:

* the trace/compile/execute wall-clock split comes from timing
  ``jit(...).lower()`` / ``.compile()`` / the compiled call separately;
* the per-phase device-time breakdown comes from capturing a
  ``jax.profiler`` trace of the execute, reading it with
  ``jax.profiler.ProfileData`` and joining each op event's HLO
  instruction against the compiled module's ``op_name`` metadata — the
  only place the phase names survive compilation.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Any

import jax

__all__ = [
    "KNOWN_PHASES",
    "PHASE_DPPS_PERTURB",
    "PHASE_DPPS_SENSITIVITY",
    "PHASE_DPPS_NOISE",
    "PHASE_DPPS_GOSSIP",
    "PHASE_DPPS_SYNC",
    "PHASE_DPPS_WIRE_STATS",
    "PHASE_PUSHSUM_MIX",
    "PHASE_GRADS_LOCAL",
    "PHASE_GRADS_SHARED",
    "PHASE_CLIP",
    "PHASE_PACK",
    "PHASE_UNPACK",
    "PHASE_FAULTS",
    "PHASE_MODEL_EMBED",
    "PHASE_MODEL_MLSTM",
    "PHASE_MODEL_SLSTM",
    "PHASE_MODEL_ATTN",
    "PHASE_MODEL_MLP",
    "PHASE_MODEL_MOE",
    "PHASE_MODEL_MAMBA2",
    "PHASE_MODEL_HEAD",
    "HOST_SPANS",
    "SPAN_API_RUN",
    "SPAN_API_TRAIN",
    "SPAN_API_STATE_INIT",
    "SPAN_API_COPY_STATE",
    "SPAN_API_HOOKS",
    "SPAN_API_WAIT",
    "SPAN_API_REPORT",
    "SPAN_API_CONSENSUS",
    "SPAN_ENGINE_INPUTS",
    "SPAN_ENGINE_DISPATCH",
    "COMPILE_EVENTS",
    "ProfileReport",
    "compile_count",
    "phase",
    "span",
    "phase_breakdown",
    "hlo_phase_map",
    "xplane_durations",
]

# Canonical phase names (one vocabulary across core/engine/net and the
# profiler output). Distinctive snake_case tokens: the join looks for them
# as path components of the op_name metadata.
PHASE_DPPS_PERTURB = "dpps_perturb"
PHASE_DPPS_SENSITIVITY = "dpps_sensitivity"
PHASE_DPPS_NOISE = "dpps_noise"
PHASE_DPPS_GOSSIP = "dpps_gossip"
PHASE_DPPS_SYNC = "dpps_sync"
PHASE_DPPS_WIRE_STATS = "dpps_wire_stats"
PHASE_PUSHSUM_MIX = "pushsum_mix"   # nests inside dpps_gossip
PHASE_GRADS_LOCAL = "partpsp_local_grads"
PHASE_GRADS_SHARED = "partpsp_shared_grads"
PHASE_CLIP = "partpsp_clip"
PHASE_PACK = "engine_pack"
PHASE_UNPACK = "engine_unpack"
PHASE_FAULTS = "net_faults"
# Model blocks (repro.models.transformer). Under jax.grad a scope opened
# at the top of the differentiated function surfaces wrapped, as
# jvp(<phase>) / transpose(jvp(<phase>)) path components; all nest inside
# the partpsp_*_grads phases.
PHASE_MODEL_EMBED = "model_embed"
PHASE_MODEL_MLSTM = "model_mlstm"
PHASE_MODEL_SLSTM = "model_slstm"
PHASE_MODEL_ATTN = "model_attn"
PHASE_MODEL_MLP = "model_mlp"
PHASE_MODEL_MOE = "model_moe"
PHASE_MODEL_MAMBA2 = "model_mamba2"
PHASE_MODEL_HEAD = "model_head"

# Registry of every phase name (insertion ordered): the canonical names
# above, then whatever else a phase() scope was entered with. The
# profiler's HLO join only attributes device time to names registered here.
KNOWN_PHASES: dict[str, None] = dict.fromkeys(
    v for k, v in tuple(globals().items()) if k.startswith("PHASE_"))

# Host spans: repro.<layer>.<what>. Every span of one Session call carries
# that call's ``call`` id (a per-session counter).
SPAN_API_RUN = "repro.api.run"                # Session.run, the whole call
SPAN_API_TRAIN = "repro.api.train"            # Session.train, the whole call
SPAN_API_STATE_INIT = "repro.api.state_init"  # state built on the host
SPAN_API_COPY_STATE = "repro.api.copy_state"  # copy of the caller's state
SPAN_API_HOOKS = "repro.api.hooks"            # prepare/consume/finish hooks
SPAN_API_WAIT = "repro.api.wait"              # the host blocked on the device
SPAN_API_REPORT = "repro.api.report"          # RunReport; stats = its counts
SPAN_API_CONSENSUS = "repro.api.consensus"    # Session.consensus readout
SPAN_ENGINE_INPUTS = "repro.engine.inputs"    # per-round inputs stacked
SPAN_ENGINE_DISPATCH = "repro.engine.dispatch"  # one segment enqueued

HOST_SPANS: dict[str, None] = dict.fromkeys(
    v for k, v in tuple(globals().items()) if k.startswith("SPAN_"))

# What counts as one compile: a lowering to MLIR and a backend compile,
# each one event (a persistent-cache hit still lowers).
COMPILE_EVENTS = ("/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration")
_compiles = 0


def _count_compile(event: str, duration: float, **kwargs) -> None:
    global _compiles
    if event in COMPILE_EVENTS:
        _compiles += 1


jax.monitoring.register_event_duration_secs_listener(_count_compile)


def compile_count() -> int:
    """Lowerings + backend compiles this process has made so far."""
    return _compiles


def phase(name: str):
    """Annotate a round phase: ``with phase("dpps_gossip"): ...``.

    Returns ``jax.named_scope(name)`` after registering ``name`` in
    :data:`KNOWN_PHASES`. Metadata-only — zero traced ops, pinned by the
    golden-HLO tests.
    """
    KNOWN_PHASES.setdefault(name)
    return jax.named_scope(name)


def span(name: str, **ids):
    """Name host work: ``with span("repro.api.wait", call=3): ...``.

    Returns a ``jax.profiler.TraceAnnotation`` after registering ``name``
    in :data:`HOST_SPANS`; ``ids`` become the trace event's stats, and
    ``set_metadata(**stats)`` on the entered span adds more before it
    closes. Host code only — never inside a traced function.
    """
    HOST_SPANS.setdefault(name)
    return jax.profiler.TraceAnnotation(name, **ids)


# ---------------------------------------------------------------------------
# Profiling: wall-clock split + per-phase device-time breakdown
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class ProfileReport:
    """One profiled segment (see :meth:`repro.api.Session.profile`).

    ``trace_s`` / ``compile_s`` / ``execute_s`` split the wall clock the
    lump-sum ``RunReport.wall_clock`` used to conflate; ``phases`` maps
    phase name -> device seconds (plus ``"unattributed"`` for device time
    outside any registered phase), summing to ``device_total_s``.
    """

    rounds: int
    backend: str
    trace_s: float
    compile_s: float
    execute_s: float
    phases: dict[str, float]
    device_total_s: float
    trace_dir: str | None = None
    note: str | None = None

    @property
    def wall_clock(self) -> float:
        return self.trace_s + self.compile_s + self.execute_s

    def summary(self) -> dict[str, Any]:
        out: dict[str, Any] = {
            "rounds": self.rounds,
            "backend": self.backend,
            "trace_s": round(self.trace_s, 4),
            "compile_s": round(self.compile_s, 4),
            "execute_s": round(self.execute_s, 4),
            "wall_clock_s": round(self.wall_clock, 4),
            "device_total_s": round(self.device_total_s, 4),
            "phases": {k: round(v, 6) for k, v in sorted(
                self.phases.items(), key=lambda kv: -kv[1])},
        }
        if self.note:
            out["note"] = self.note
        return out


_INSTR_RE = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=")
_OP_NAME_RE = re.compile(r'op_name="([^"]*)"')


def hlo_phase_map(hlo_text: str) -> dict[str, str]:
    """Compiled HLO text -> {instruction name: phase name}.

    An instruction belongs to a phase when any :data:`KNOWN_PHASES` name
    appears as a path component of its ``op_name`` metadata (named scopes
    become path components; fused instructions carry a representative
    constituent's op_name, which is attribution enough for a breakdown).
    """
    phases = set(KNOWN_PHASES)
    out: dict[str, str] = {}
    if not phases:
        return out
    for line in hlo_text.splitlines():
        op_name = _OP_NAME_RE.search(line)
        if op_name is None:
            continue
        instr = _INSTR_RE.match(line)
        if instr is None:
            continue
        for part in op_name.group(1).split("/"):
            if part in phases:
                out[instr.group(1)] = part
                break
    return out


def _xplane_files(trace_dir: str) -> list[str]:
    return sorted(glob.glob(
        os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True))


_EVENT_INSTR_RE = re.compile(r"^%?([\w.\-]+)\s*=")


def _op_events(profile) -> list[tuple[str, float, float]]:
    """(instruction, start_ns, duration_ns) of every op execution.

    On an accelerator these are the ``XLA Ops`` line of each ``/device:``
    plane, each event named by its HLO instruction text; on the CPU, the
    host events that carry an ``hlo_op`` stat. An op whose interval holds
    the next one on its line is a loop or conditional around its body and
    is left out, so no time counts twice.
    """
    device = [ln for p in profile.planes if p.name.startswith("/device:")
              for ln in p.lines if ln.name == "XLA Ops"]
    lines = device or [ln for p in profile.planes
                       if not p.name.startswith("/device:")
                       for ln in p.lines]
    out = []
    for line in lines:
        ops = []
        for e in line.events:
            if device:
                m = _EVENT_INSTR_RE.match(e.name)
                ops.append((m.group(1) if m else e.name, e.start_ns,
                            e.duration_ns))
                continue
            hlo_op = next((v for k, v in e.stats if k == "hlo_op"), None)
            if hlo_op:
                ops.append((str(hlo_op), e.start_ns, e.duration_ns))
        ops.sort(key=lambda o: o[1])
        out += [o for o, nxt in zip(ops, ops[1:] + [None])
                if nxt is None or nxt[1] >= o[1] + o[2]]
    return out


def xplane_durations(trace_dir: str) -> dict[str, int] | None:
    """Profiler trace dir -> {hlo instruction name: duration_ps summed}.

    Read with ``jax.profiler.ProfileData`` (JAX alone). Returns ``None``
    when no trace file was written or it holds no op execution — callers
    degrade to an empty breakdown with a note.
    """
    from jax.profiler import ProfileData

    durations: dict[str, int] = {}
    for path in _xplane_files(trace_dir):
        for name, _, dur_ns in _op_events(ProfileData.from_file(path)):
            durations[name] = durations.get(name, 0) + round(dur_ns * 1e3)
    return durations or None


def phase_breakdown(
    hlo_text: str, trace_dir: str
) -> tuple[dict[str, float], float, str | None]:
    """Join a profiler trace against compiled HLO metadata.

    Returns ``(phases, device_total_s, note)`` where ``phases`` maps each
    registered phase (plus ``"unattributed"``) to device seconds.
    """
    durations = xplane_durations(trace_dir)
    if durations is None:
        return {}, 0.0, ("no op executions in the profiler trace; "
                         "wall-clock split only")
    instr_phase = hlo_phase_map(hlo_text)
    phases: dict[str, float] = {}
    total = 0.0
    for instr, ps in durations.items():
        seconds = ps * 1e-12
        total += seconds
        key = instr_phase.get(instr, "unattributed")
        phases[key] = phases.get(key, 0.0) + seconds
    return phases, total, None
