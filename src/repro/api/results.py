"""Typed run results for the `repro.api` front door.

Every session driver returns a :class:`RunReport` (protocol/training runs)
or a :class:`ServeReport` (decode runs) instead of the bare
``(state, trajectory)`` tuples the engine produces — so consumers read
"what did this run cost" (epsilon spent, wire bytes, wall-clock) off one
object instead of re-deriving it from configs in every driver.

The wire-byte figure is an *estimate* of the protocol's network traffic:
each round every node transmits its noised message (``d_s`` elements in
the plan's wire dtype), its push-sum weight, and its sensitivity scalar to
each out-neighbour (paper Alg. 1 lines 4/6; Eq. 9). It deliberately counts
payload only — no framing/transport overhead — so schedule and wire-dtype
comparisons stay apples-to-apples (EXPERIMENTS.md SPerf #1).
"""
from __future__ import annotations

import dataclasses
from typing import Any

import numpy as np

__all__ = ["RunReport", "ServeReport", "estimate_wire_bytes"]


def estimate_wire_bytes(plan, n_nodes: int, d_s: int, rounds: int) -> int:
    """Estimated protocol payload bytes for ``rounds`` rounds (see module
    docstring). ``plan`` may be None (loop runs without a plan): dense
    all-to-all f32 is assumed. Self-loops (circulant offset 0, the dense
    diagonal) never cross the wire and are excluded."""
    codec = getattr(plan, "wire", None) if plan is not None else None
    if codec is not None and getattr(codec, "active", False):
        # An active wire codec owns the payload accounting (repro.wire):
        # int8 = d_s + 4 (coords + per-node scale), topk = 6k (f32 value
        # + uint16 index per kept coordinate), bf16 = 2 d_s. The ledger,
        # NetworkStatsHook and BENCH_wire.json all read this same figure.
        payload = int(codec.payload_bytes(d_s))
    else:
        per_elem = 2 if plan is not None and plan.wire_dtype == "bf16" else 4
        payload = d_s * per_elem
    if plan is not None and plan.schedule == "circulant" and plan.offsets:
        edges_per_round = n_nodes * sum(
            1 for o in plan.offsets if o % n_nodes != 0)
    elif plan is not None and getattr(plan, "sparse_idx", None) is not None:
        # Edge-list plans pay only for the nominal non-self edges (mean
        # over the period) — the whole point of the sparse schedule.
        import numpy as np

        idx = np.asarray(plan.sparse_idx)            # (P, N, K)
        vals = np.asarray(plan.sparse_vals)
        recv = np.arange(idx.shape[1])[None, :, None]
        nonself = (vals > 0.0) & (idx != recv)
        edges_per_round = float(nonself.sum()) / idx.shape[0]
    else:
        edges_per_round = n_nodes * (n_nodes - 1)
    # message payload + push-sum weight a_i (f32) + sensitivity scalar S_i
    # (f32, broadcast for the Alg. 1 line-4 max)
    per_round = edges_per_round * (payload + 4 + 4)
    return int(int(rounds) * per_round)


@dataclasses.dataclass
class RunReport:
    """What a :meth:`ProtocolSession.run` / :meth:`ProtocolSession.train`
    call did.

    Fields:
      state          final protocol/training state (resume seed for the
                     next segment or checkpoint payload).
      trajectory     per-round metric trajectory, leaves (rounds, ...)
                     concatenated across scan segments (host numpy).
      rounds         rounds actually executed (< requested on a strict
                     budget abort).
      epsilon_spent  composed epsilon of the executed protected rounds
                     (pure-DP linear composition; sync rounds excluded).
      wire_bytes     estimated protocol payload traffic (module docstring).
      compile_s      wall seconds of the *first* segment — tracing + XLA
                     compilation + its execution (synced) — when the call
                     compiled; 0 when it compiled nothing (no sync made).
      run_s          wall seconds of everything after: the steady-state
                     segments plus host-side hook consumption. Per-round
                     timing figures should use this (see
                     benchmarks/table4_time.py), not the lump sum.
      wall_clock     derived property: ``compile_s + run_s`` (the lump
                     sum older callers read).
      aborted        True when a hook aborted the run (strict privacy
                     budget, strict watchdog); ``abort_reason`` carries
                     the message.
      network        realized-network record
                     (:class:`repro.net.stats.NetworkStats`) when a
                     ``NetworkStatsHook`` was attached — the per-round
                     realized edges / dropped edges / B-window
                     connectivity under fault injection. ``wire_bytes``
                     above stays the *nominal* plan estimate;
                     ``network.effective_bytes`` is what actually crossed
                     the wire.
      counts         what the call asked of the runtime:
                     ``dispatches`` (compiled segments enqueued),
                     ``host_syncs`` (times the host waited on the device:
                     the first-segment sync of a compiling call, every
                     per-segment sync a span hook asks for, and the one
                     batched sync that reads the whole trajectory back),
                     ``readback_leaves`` (the trajectory leaves of every
                     segment that batched sync fetched) and ``compiles``
                     (the change over the call in the process-wide count
                     of lowerings + backend compiles). The call's
                     ``repro.api.report`` host span carries them as stats.
    """

    state: Any
    trajectory: dict[str, Any]
    rounds: int
    epsilon_spent: float
    wire_bytes: int
    compile_s: float = 0.0
    run_s: float = 0.0
    aborted: bool = False
    abort_reason: str | None = None
    network: Any = None
    counts: dict[str, int] = dataclasses.field(default_factory=dict)

    @property
    def wall_clock(self) -> float:
        return self.compile_s + self.run_s

    def summary(self) -> dict[str, Any]:
        eps = float(self.epsilon_spent)
        out = {
            "rounds": self.rounds,
            "epsilon_spent": eps if np.isfinite(eps) else None,
            "wire_bytes": self.wire_bytes,
            "compile_s": round(self.compile_s, 3),
            "run_s": round(self.run_s, 3),
            "wall_clock_s": round(self.wall_clock, 3),
            "aborted": self.aborted,
            "counts": dict(self.counts),
        }
        if self.network is not None:
            out["network"] = self.network.summary()
        return out


@dataclasses.dataclass
class ServeReport:
    """One batched prefill + scan-compiled decode pass.

    ``tokens`` is the full generated sequence per batch row, shape
    ``(batch, gen)`` — the argmax first token followed by the sampled
    continuation (the decode hot loop is ``repro.engine.run_decode``: one
    dispatch for the whole generation).
    """

    tokens: Any
    prefill_s: float
    decode_s: float
    steps: int

    @property
    def ms_per_token(self) -> float:
        return self.decode_s / max(self.steps, 1) * 1e3
