"""``BENCHMARK.json`` and the files it names, found by name.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric sits in a file of its own under the benchmark directory:

* ``configs/<config>.json``  — sizes, deployment and the precision it states;
* ``traffic/<mix>.json``     — the mix's parameters; its ``kind`` names the
  generator that reads them, ``kinds/<kind>.py`` (``drive``, ``controls``);
* ``metrics/<metric>.py``    — a reader with ``read(view) -> float | None``;
* ``values/<kind>.py``, ``graphs/<kind>.py`` — private values and the
  reference's mixing weights, named by a configuration.

Adding a cell therefore means adding files and manifest entries, never
editing a file that is already there: a new mix of a known kind is one data
file, a new kind of traffic one more module.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import pathlib
import re
from typing import Any, Callable

BENCH_DIR = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parent
MANIFEST = ROOT / "BENCHMARK.json"

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


@dataclasses.dataclass(frozen=True)
class Cell:
    """One workload of the manifest with everything it needs resolved."""

    name: str
    chips: int
    config: dict[str, Any]
    traffic: dict[str, Any]
    end_to_end: tuple[dict[str, Any], ...]
    per_layer: tuple[dict[str, Any], ...]
    bench_dir: pathlib.Path

    def reader(self, metric: str) -> Callable[[Any], float | None]:
        return load_reader(self.bench_dir, metric)

    def kind(self):
        """The traffic generator module ``kinds/<kind>.py`` of this mix."""
        return load_module(self.bench_dir, "kinds", self.traffic["kind"])


def load_manifest(path: pathlib.Path | str = MANIFEST) -> dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def _json_file(bench_dir: pathlib.Path, sub: str, name: str) -> dict:
    path = bench_dir / sub / f"{name}.json"
    if not path.is_file():
        raise FileNotFoundError(f"no {sub} file for {name!r}: {path}")
    with open(path) as f:
        return json.load(f)


def load_config(bench_dir: pathlib.Path, name: str) -> dict[str, Any]:
    return _json_file(bench_dir, "configs", name)


def load_traffic(bench_dir: pathlib.Path, name: str) -> dict[str, Any]:
    return _json_file(bench_dir, "traffic", name)


_MODULES: dict[pathlib.Path, Any] = {}


def load_module(bench_dir: pathlib.Path, sub: str, name: str):
    """``<sub>/<name>.py`` as a module (names may hold dots), loaded once."""
    path = (pathlib.Path(bench_dir) / sub / f"{name}.py").resolve()
    if path not in _MODULES:
        if not path.is_file():
            raise FileNotFoundError(f"no {sub} module for {name!r}: {path}")
        spec = importlib.util.spec_from_file_location(
            f"bench_{sub}_" + re.sub(r"\W", "_", name), path)
        module = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(module)
        _MODULES[path] = module
    return _MODULES[path]


def load_reader(bench_dir: pathlib.Path, metric: str):
    """``metrics/<metric>.py``'s ``read`` function."""
    return load_module(bench_dir, "metrics", metric).read


def metrics_for(manifest: dict, section: str, workload: dict) -> list[dict]:
    """The metrics of ``section`` that ``workload`` reports.

    A metric with a ``workloads`` key lists its cells. A per-layer metric
    without one is reported wherever its ``moves`` metric is; an end-to-end
    metric without one is reported by every cell.
    """
    e2e = [m for m in manifest["end_to_end"]
           if workload["name"] in m.get("workloads", [workload["name"]])]
    if section == "end_to_end":
        return e2e
    e2e_names = {m["name"] for m in e2e}
    out = []
    for m in manifest["per_layer"]:
        if "workloads" in m:
            if workload["name"] in m["workloads"]:
                out.append(m)
        elif m["moves"] in e2e_names:
            out.append(m)
    return out


def resolve(workload_name: str, manifest_path: pathlib.Path | str = MANIFEST,
            bench_dir: pathlib.Path | None = None) -> Cell:
    manifest = load_manifest(manifest_path)
    bench_dir = BENCH_DIR if bench_dir is None else pathlib.Path(bench_dir)
    cells = {w["name"]: w for w in manifest["workloads"]}
    if workload_name not in cells:
        raise KeyError(f"no workload {workload_name!r} in {manifest_path}; "
                       f"have {sorted(cells)}")
    w = cells[workload_name]
    return Cell(
        name=w["name"], chips=int(w["chips"]),
        config=load_config(bench_dir, w["config"]),
        traffic=load_traffic(bench_dir, w["traffic"]),
        end_to_end=tuple(metrics_for(manifest, "end_to_end", w)),
        per_layer=tuple(metrics_for(manifest, "per_layer", w)),
        bench_dir=bench_dir)
