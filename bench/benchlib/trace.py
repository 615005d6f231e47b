"""Reduce one profiler trace of the measured window to device numbers.

* The trace is read with ``jax.profiler.ProfileData`` (JAX alone).
* Device busy time is the union of the intervals in which an operation ran
  on a chip (the ``XLA Ops`` line of each ``/device:TPU:n`` plane), clipped
  to the window the harness marks with its ``bench.window`` span.
* Per-phase device time joins each op event (named by its HLO instruction)
  to the phase in that instruction's ``op_name`` metadata in the compiled
  HLO text of the program the window drives (``jax.named_scope`` path
  components survive there) — a copy of the program's join, kept here so
  the yardstick cannot move with the program. Only ops inside that
  program's module (the ``XLA Modules`` line) are joined.
* ``breakdown`` lists the device ops that took most time and the longest
  idle gaps, each gap named by the innermost ``bench.*`` host span around it.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re

# The program's phase vocabulary (repro.obs.trace). Names are matched as
# path components of the op_name metadata; the outermost one wins.
PHASES = (
    "dpps_perturb", "dpps_sensitivity", "dpps_noise", "dpps_gossip",
    "dpps_sync", "dpps_wire_stats", "pushsum_mix", "partpsp_local_grads",
    "partpsp_shared_grads", "partpsp_clip", "engine_pack", "engine_unpack",
    "net_faults",
)
WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."

_INSTR_RE = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=")
_OP_NAME_RE = re.compile(r'op_name="([^"]*)"')


def phase_of_path(path: str) -> str | None:
    for part in path.split("/"):
        if part in PHASES:
            return part
    return None


def hlo_phase_map(hlo_text: str) -> dict[str, str]:
    """Compiled HLO text -> {instruction name: phase}."""
    out: dict[str, str] = {}
    for line in hlo_text.splitlines():
        op_name = _OP_NAME_RE.search(line)
        instr = _INSTR_RE.match(line) if op_name else None
        if instr is None:
            continue
        ph = phase_of_path(op_name.group(1))
        if ph is not None:
            out[instr.group(1)] = ph
    return out


@dataclasses.dataclass
class TraceSummary:
    window_s: float                 # traced window length
    busy_s: float                   # device busy, averaged over the chips
    phase_s: dict[str, float]       # device seconds per phase, all chips
    device_s: list[float]           # per chip: summed op durations
    device_ops: list[list]          # [[name, seconds], ...] top 10
    idle_gaps: list[list]           # [[host span, seconds], ...] top 10
    n_ops: int

    def breakdown(self) -> dict:
        return {"device_ops": self.device_ops, "idle_gaps": self.idle_gaps}


def _union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    merged: list[list[float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


_EVENT_INSTR_RE = re.compile(r"^%?([\w.\-]+)\s*=")
_MODULE_RE = re.compile(r"^\s*HloModule\s+([\w.\-]+)", re.MULTILINE)


def instruction(event_name: str) -> str:
    """``%fusion.12 = f32[8] fusion(...)`` -> ``fusion.12``: the TPU trace
    names each op event by its HLO instruction text."""
    m = _EVENT_INSTR_RE.match(event_name)
    return m.group(1) if m else event_name


def _module_base(name: str) -> str:
    return name.split("(", 1)[0]


def load(trace_dir: str):
    from jax.profiler import ProfileData

    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return ProfileData.from_file(files[-1])


def device_planes(profile, n_devices: int) -> list:
    planes = [p for p in profile.planes
              if re.fullmatch(r"/device:TPU:\d+", p.name)]
    planes.sort(key=lambda p: int(p.name.rsplit(":", 1)[1]))
    return planes[:n_devices]


def reduce(profile, *, n_devices: int, hlo_texts: tuple[str, ...] = ()
           ) -> TraceSummary:
    spans: list[tuple[float, float, str]] = []
    for plane in profile.planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(SPAN_PREFIX):
                    spans.append((e.start_ns, e.start_ns + e.duration_ns,
                                  e.name))
    windows = [s for s in spans if s[2] == WINDOW_SPAN]
    if not windows:
        raise ValueError(f"trace has no {WINDOW_SPAN!r} host span")
    w0, w1 = windows[0][0], windows[0][1]
    inner = [s for s in spans if s[2] != WINDOW_SPAN]

    hlo_map: dict[str, str] = {}
    modules: set[str] = set()
    for text in hlo_texts:
        hlo_map.update(hlo_phase_map(text))
        m = _MODULE_RE.search(text)
        if m:
            modules.add(m.group(1))

    phase_s: dict[str, float] = {}
    op_s: dict[str, float] = {}
    busy, dev_s, gaps = [], [], []
    n_ops = 0
    for plane in device_planes(profile, n_devices):
        ops = [e for ln in plane.lines if ln.name == "XLA Ops"
               for e in ln.events]
        ops.sort(key=lambda e: e.start_ns)
        # The ops of one program run one after another on the chip; an op
        # whose interval holds the next one is a loop or conditional
        # around its body, counted in the busy union but not as an op.
        mods = sorted(((e.start_ns, e.start_ns + e.duration_ns,
                        _module_base(e.name))
                       for ln in plane.lines if ln.name == "XLA Modules"
                       for e in ln.events))
        mod_starts = [m[0] for m in mods]
        intervals = []
        d_s = 0.0
        for i, e in enumerate(ops):
            a = max(e.start_ns, w0)
            b = min(e.start_ns + e.duration_ns, w1)
            if b <= a:
                continue
            intervals.append((a, b))
            end_e = e.start_ns + e.duration_ns
            if i + 1 < len(ops) and ops[i + 1].start_ns < end_e:
                continue
            n_ops += 1
            sec = (b - a) * 1e-9
            name = instruction(e.name)
            k = bisect.bisect_right(mod_starts, e.start_ns) - 1
            module = mods[k][2] if k >= 0 and e.start_ns < mods[k][1] else ""
            ph = hlo_map.get(name) if (not modules or module in modules) \
                else None
            if ph is not None:
                phase_s[ph] = phase_s.get(ph, 0.0) + sec
            key = f"{ph}/{name}" if ph else name
            op_s[key] = op_s.get(key, 0.0) + sec
            d_s += sec
        merged = _union(intervals)
        busy.append(sum(b - a for a, b in merged) * 1e-9)
        dev_s.append(d_s)
        edges = [w0] + [x for ab in merged for x in ab] + [w1]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b > a:
                gaps.append((a, b))
    if not busy:
        raise ValueError("trace has no TPU device plane with XLA Ops")

    def gap_name(a: float, b: float) -> str:
        mid = 0.5 * (a + b)
        around = [s for s in inner if s[0] <= mid <= s[1]]
        if not around:
            return "untraced_host"
        s = min(around, key=lambda s: s[1] - s[0])
        return s[2][len(SPAN_PREFIX):]

    gaps.sort(key=lambda ab: ab[0] - ab[1])
    idle = [[gap_name(a, b), (b - a) * 1e-9] for a, b in gaps[:10]]
    top = sorted(op_s.items(), key=lambda kv: -kv[1])[:10]
    return TraceSummary(
        window_s=(w1 - w0) * 1e-9, busy_s=sum(busy) / len(busy),
        phase_s=phase_s, device_s=dev_s, device_ops=[[k, v] for k, v in top],
        idle_gaps=idle, n_ops=n_ops)
