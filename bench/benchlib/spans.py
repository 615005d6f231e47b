"""Name the chip's idle time in the measured window by the host work over it.

Read from the same ``jax.profiler.ProfileData`` as ``trace.reduce``:

* Each chip's idle intervals inside ``bench.window`` are formed exactly as
  ``trace.reduce`` forms them: the window less the union of its ``XLA Ops``.
* The parts of an idle interval that lie inside an ``XLA Modules`` event of
  that chip are idle between the ops of a running program: the host did
  not cause them. They form the ``in_program`` bucket.
* Every other, host-bound, part is split by overlap across the innermost
  host span over it: the program's innermost ``repro.*`` span if one
  covers the part, else the innermost ``bench.*`` span, else ``untraced``.
* ``report_counts`` sums the counts that the program's ``repro.api.report``
  spans carry as stats.
* ``model_phase_s`` joins device ops to the model's ``model_*`` scopes,
  which transformations wrap inside a path component:
  ``jvp(vmap(model_head))``, ``transpose(jvp(vmap(model_embed)))``,
  ``transpose;model_slstm``.
"""
from __future__ import annotations

import bisect
import re

from benchlib import trace

PROGRAM_PREFIX = "repro."
REPORT_SPAN = "repro.api.report"
IN_PROGRAM = "in_program"
UNTRACED = "untraced"
# The program's model scopes (repro.obs.trace PHASE_MODEL_*).
MODEL_PHASES = (
    "model_embed", "model_mlstm", "model_slstm", "model_attn", "model_mlp",
    "model_moe", "model_mamba2", "model_head",
)
_WORD_RE = re.compile(r"\w+")


def host_spans(profile) -> list[tuple]:
    """Every ``repro.*`` and ``bench.*`` span on the host planes."""
    out = []
    for plane in profile.planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith((PROGRAM_PREFIX, trace.SPAN_PREFIX)):
                    out.append((e.start_ns, e.start_ns + e.duration_ns,
                                e.name, dict(e.stats)))
    return out


def _window(spans: list[tuple]) -> tuple[float, float]:
    for s in spans:
        if s[2] == trace.WINDOW_SPAN:
            return s[0], s[1]
    raise ValueError(f"trace has no {trace.WINDOW_SPAN!r} host span")


def _innermost(active: list[tuple]) -> str:
    program = [s for s in active if s[2].startswith(PROGRAM_PREFIX)]
    pool = program or active
    if not pool:
        return UNTRACED
    return min(pool, key=lambda s: s[1] - s[0])[2]


def labels(spans: list[tuple], w0: float, w1: float
           ) -> list[tuple[float, float, str]]:
    """The window cut at every span boundary inside it, each piece named
    by the innermost span over it (``_innermost``)."""
    inner = sorted((s for s in spans if s[2] != trace.WINDOW_SPAN
                    and s[1] > w0 and s[0] < w1), key=lambda s: s[0])
    points = sorted({w0, w1} | {min(max(x, w0), w1)
                                for s in inner for x in s[:2]})
    out, active, i = [], [], 0
    for a, b in zip(points, points[1:]):
        while i < len(inner) and inner[i][0] <= a:
            active.append(inner[i])
            i += 1
        active = [s for s in active if s[1] > a]
        out.append((a, b, _innermost(active)))
    return out


def _overlaps(a: float, b: float, intervals: list[tuple[float, float]],
              starts: list[float]):
    """(lo, hi, k) for each of the sorted, disjoint ``intervals`` that
    overlaps [a, b), clipped to it."""
    k = max(bisect.bisect_right(starts, a) - 1, 0)
    while k < len(intervals) and intervals[k][0] < b:
        lo, hi = max(a, intervals[k][0]), min(b, intervals[k][1])
        if hi > lo:
            yield lo, hi, k
        k += 1


def idle_by_span(profile, *, n_devices: int, longer_than: float = 0.0
                 ) -> dict[str, float]:
    """{bucket: idle seconds in the window}, averaged over the chips as
    ``trace.reduce`` averages busy time; the buckets sum to the window less
    the busy time. ``longer_than`` (seconds) keeps only the idle intervals
    longer than that, such as the one long gap a job."""
    spans = host_spans(profile)
    w0, w1 = _window(spans)
    pieces = labels(spans, w0, w1)
    piece_starts = [p[0] for p in pieces]
    out: dict[str, float] = {}
    planes = trace.device_planes(profile, n_devices)
    if not planes:
        raise ValueError("trace has no TPU device plane")
    for plane in planes:
        events = {ln.name: ln.events for ln in plane.lines}
        clipped = [(max(e.start_ns, w0), min(e.start_ns + e.duration_ns, w1))
                   for e in events.get("XLA Ops", ())]
        busy = trace._union([ab for ab in clipped if ab[1] > ab[0]])
        edges = [w0] + [x for ab in busy for x in ab] + [w1]
        mods = trace._union([(e.start_ns, e.start_ns + e.duration_ns)
                             for e in events.get("XLA Modules", ())])
        mod_starts = [m[0] for m in mods]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b - a <= longer_than * 1e9:
                continue
            cut = a
            host = []
            for lo, hi, _ in _overlaps(a, b, mods, mod_starts):
                out[IN_PROGRAM] = out.get(IN_PROGRAM, 0.0) + (hi - lo)
                host.append((cut, lo))
                cut = hi
            host.append((cut, b))
            for x, y in host:
                if y <= x:
                    continue
                for lo, hi, k in _overlaps(x, y, pieces, piece_starts):
                    name = pieces[k][2]
                    out[name] = out.get(name, 0.0) + (hi - lo)
    return {k: v * 1e-9 / len(planes) for k, v in out.items()}


def report_counts(profile) -> dict[str, float]:
    """The summed stats of the ``repro.api.report`` spans that start inside
    the window, and how many there were (``reports``)."""
    spans = host_spans(profile)
    w0, w1 = _window(spans)
    out = {"reports": 0.0}
    for a, _, name, stats in spans:
        if name == REPORT_SPAN and w0 <= a < w1:
            out["reports"] += 1
            for k, v in stats.items():
                if k != "call":
                    out[k] = out.get(k, 0.0) + float(v)
    return out


def model_phase_of_path(path: str) -> str | None:
    """The outermost ``model_*`` phase in the path: the first component
    that holds one as a whole word, whatever transformations wrap it."""
    for part in path.split("/"):
        for word in _WORD_RE.findall(part):
            if word in MODEL_PHASES:
                return word
    return None


def model_phase_map(hlo_text: str) -> dict[str, str]:
    """Compiled HLO text -> {instruction name: model phase}."""
    out: dict[str, str] = {}
    for line in hlo_text.splitlines():
        op_name = trace._OP_NAME_RE.search(line)
        instr = trace._INSTR_RE.match(line) if op_name else None
        if instr is None:
            continue
        ph = model_phase_of_path(op_name.group(1))
        if ph is not None:
            out[instr.group(1)] = ph
    return out


def model_phase_s(profile, *, n_devices: int, hlo_texts: tuple[str, ...]
                  ) -> dict[str, float]:
    """Device seconds per model phase in the window, all chips, over the
    ops of the window's own program (as ``trace.reduce`` joins phases)."""
    w0, w1 = _window(host_spans(profile))
    hlo_map: dict[str, str] = {}
    modules: set[str] = set()
    for text in hlo_texts:
        hlo_map.update(model_phase_map(text))
        m = trace._MODULE_RE.search(text)
        if m:
            modules.add(m.group(1))
    out: dict[str, float] = {}
    for plane in trace.device_planes(profile, n_devices):
        events = {ln.name: ln.events for ln in plane.lines}
        ops = sorted(events.get("XLA Ops", ()), key=lambda e: e.start_ns)
        mods = sorted((e.start_ns, e.start_ns + e.duration_ns,
                       trace._module_base(e.name))
                      for e in events.get("XLA Modules", ()))
        mod_starts = [m[0] for m in mods]
        for i, e in enumerate(ops):
            end = e.start_ns + e.duration_ns
            a, b = max(e.start_ns, w0), min(end, w1)
            if b <= a or (i + 1 < len(ops) and ops[i + 1].start_ns < end):
                continue
            k = bisect.bisect_right(mod_starts, e.start_ns) - 1
            module = mods[k][2] if k >= 0 and e.start_ns < mods[k][1] else ""
            if modules and module not in modules:
                continue
            ph = hlo_map.get(trace.instruction(e.name))
            if ph is not None:
                out[ph] = out.get(ph, 0.0) + (b - a) * 1e-9
    return out
