"""The chip: refuse anything but a TPU, look up its peaks, read its memory."""
from __future__ import annotations

import json
import pathlib

from benchlib.manifest import BENCH_DIR

PEAKS = BENCH_DIR / "peaks.json"


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def chips(n: int, *, require_tpu: bool = True) -> list:
    """The first ``n`` devices; raises :class:`NoChip` unless they are TPUs."""
    import jax

    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise NoChip(f"JAX's first device is {devs[0].platform!r}, not a TPU")
    if len(devs) < n:
        raise NoChip(f"the cell needs {n} chips, JAX found {len(devs)}")
    return devs[:n]


def record(devs: list) -> dict:
    """The result line's ``device`` object (``memory_peak_bytes`` is the
    peak on the fullest chip, where the backend reports it)."""
    peaks = []
    for d in devs:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs),
            "memory_peak_bytes": max(peaks) if peaks else 0}


def peaks(device_kind: str, path: pathlib.Path = PEAKS) -> dict:
    """Peak table row for ``device_kind``; an unknown device is an error."""
    with open(path) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in {path}; "
                       f"known: {sorted(table)}")
    return table[device_kind]
