"""BENCHMARK.json and the files it names: the contract's shape, lookup by
name, and that a new cell needs only new files and manifest entries."""
import json
import pathlib
import shutil
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "bench"), str(ROOT / "src")]

from benchlib import manifest  # noqa: E402

M = manifest.load_manifest()
ENTRY_KEYS = {
    "configs": {"name", "source", "file", "reduced", "why"},
    "workloads": {"name", "config", "traffic", "chips", "why"},
    "end_to_end": {"name", "unit", "better", "bound", "source"},
    "per_layer": {"name", "unit", "better", "source", "layer", "moves"},
}


def test_top_level_keys():
    assert set(M) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert M["command"] == ["python3", "bench/run.py"]
    assert 1 <= M["run_seconds"] <= 51
    for p in M["paths"]:
        assert (ROOT / p).is_dir()
    assert len(json.dumps(M)) <= 64 * 1024


@pytest.mark.parametrize("section", sorted(ENTRY_KEYS))
def test_entries_have_exactly_their_keys(section):
    for e in M[section]:
        extra = set(e) - ENTRY_KEYS[section]
        assert set(e) >= ENTRY_KEYS[section], e
        assert extra <= {"workloads"} and (not extra or section in (
            "end_to_end", "per_layer")), e


def test_names_and_units_use_allowed_characters():
    names = []
    for section in ENTRY_KEYS:
        for e in M[section]:
            names.append(e["name"])
            assert manifest.NAME_RE.match(e["name"]), e["name"]
            if "unit" in e:
                assert manifest.UNIT_RE.match(e["unit"]), e["unit"]
                assert len(e["unit"]) <= 16
                assert e["better"] in ("lower", "higher")
    for w in M["workloads"]:
        assert manifest.NAME_RE.match(w["config"])
        assert manifest.NAME_RE.match(w["traffic"])
    for c in M["configs"]:
        for key in c["reduced"]:
            assert manifest.NAME_RE.match(key)
    metric_names = [m["name"] for m in M["end_to_end"] + M["per_layer"]]
    assert len(set(metric_names)) == len(metric_names)
    assert len({w["name"] for w in M["workloads"]}) == len(M["workloads"])


def test_metric_rules():
    e2e = {m["name"]: m for m in M["end_to_end"]}
    assert e2e["setup_s"]["bound"] <= 0.25
    for m in M["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in M["per_layer"]:
        assert m["moves"] in e2e
        assert "bound" not in m
        cells = {w["name"] for w in M["workloads"]}
        for w in m.get("workloads", []):
            assert w in cells
            assert w in e2e[m["moves"]].get("workloads", [w])
    pairs = [(w["config"], w["traffic"]) for w in M["workloads"]]
    assert len(set(pairs)) == len(pairs)


@pytest.mark.parametrize("cell", [w["name"] for w in M["workloads"]])
def test_every_cell_finds_its_files(cell):
    c = manifest.resolve(cell)
    assert c.config and c.traffic["kind"]
    names = {m["name"] for m in c.end_to_end}
    assert "setup_s" in names and len(names) >= 2
    assert c.per_layer
    for m in c.per_layer:
        assert callable(c.reader(m["name"]))
    listed = {x["name"]: x for x in M["configs"]}
    cfg_file = ROOT / listed[c.config["name"]]["file"]
    assert json.loads(cfg_file.read_text()) == c.config


def _copy_bench(tmp_path):
    bench = tmp_path / "bench"
    for sub in ("configs", "traffic", "metrics", "kinds", "values", "graphs"):
        shutil.copytree(ROOT / "bench" / sub, bench / sub,
                        ignore=shutil.ignore_patterns("__pycache__"))
    return bench


def test_a_new_cell_is_files_and_entries_only(tmp_path):
    bench = _copy_bench(tmp_path)
    cfg = json.loads((bench / "configs" / "paper-mlp-n16.json").read_text())
    cfg["name"] = "dummy-config"
    (bench / "configs" / "dummy-config.json").write_text(json.dumps(cfg))
    (bench / "traffic" / "dummy-mix.json").write_text(json.dumps(
        {"kind": "dpps_jobs", "rounds": 3, "segment": 3, "check_jobs": 1,
         "trace_seconds": 1, "limits": {"y_gap": 1.0}}))
    (bench / "metrics" / "dummy_metric.consensus.py").write_text(
        "def read(view):\n    return 42.0\n")
    m = json.loads(json.dumps(M))
    m["configs"].append({"name": "dummy-config", "source": "x",
                         "file": "bench/configs/dummy-config.json",
                         "reduced": [], "why": "x"})
    m["workloads"].append({"name": "dummy-cell", "config": "dummy-config",
                           "traffic": "dummy-mix", "chips": 1, "why": "x"})
    for e in m["end_to_end"]:
        if e["name"] == "consensus_rounds_per_s":
            e["workloads"].append("dummy-cell")
    m["per_layer"].append({"name": "dummy_metric.consensus", "unit": "%",
                           "better": "higher", "source": "program_counter",
                           "layer": "api", "moves": "consensus_rounds_per_s",
                           "workloads": ["dummy-cell"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(m))
    cell = manifest.resolve("dummy-cell", tmp_path / "BENCHMARK.json", bench)
    assert cell.config["name"] == "dummy-config"
    assert cell.traffic["rounds"] == 3
    assert [x["name"] for x in cell.per_layer] == ["dummy_metric.consensus"]
    assert cell.reader("dummy_metric.consensus")({}) == 42.0
    # The cells already there resolve as before.
    for w in M["workloads"]:
        before = manifest.resolve(w["name"])
        after = manifest.resolve(w["name"], tmp_path / "BENCHMARK.json", bench)
        assert before.config == after.config
        assert before.traffic == after.traffic


DUMMY_KIND = '''
from benchlib import device
from benchlib.harness import Outcome


def drive(cell, seed, seconds, trace_dir, devs, t_start):
    return Outcome(setup_s=0.25, metrics={"consensus_rounds_per_s":
                                          float(cell.traffic["rate"])},
                   attempted=1, failed=0,
                   numbers={"y_gap": 0.0, "window_compiles": 0.0},
                   view={}, device=device.record(devs))
'''


def test_a_new_traffic_kind_is_one_more_file(tmp_path):
    """A mix of a kind the harness has never seen runs end to end once its
    module sits under kinds/ — no file that is there is edited."""
    from benchlib import cli

    bench = _copy_bench(tmp_path)
    (bench / "kinds" / "dummy_kind.py").write_text(DUMMY_KIND)
    (bench / "traffic" / "dummy-mix.json").write_text(json.dumps(
        {"kind": "dummy_kind", "rate": 123.0, "limits": {"y_gap": 1.0}}))
    m = json.loads(json.dumps(M))
    m["workloads"].append({"name": "dummy-cell", "config": "paper-mlp-n16",
                           "traffic": "dummy-mix", "chips": 1, "why": "x"})
    for e in m["end_to_end"]:
        if e["name"] == "consensus_rounds_per_s":
            e["workloads"].append("dummy-cell")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(m))
    args = cli.parse(["--workload", "dummy-cell", "--seed", "1",
                      "--seconds", "1", "--trace", "0",
                      "--manifest", str(tmp_path / "BENCHMARK.json")])
    result = cli.run_cell(args, 0.0, require_tpu=False)
    assert result["correct"]
    assert result["metrics"]["consensus_rounds_per_s"]["value"] == 123.0
    assert result["metrics"]["setup_s"]["value"] == 0.25


@pytest.mark.parametrize("sub,name", [("kinds", "partpsp_train"),
                                      ("kinds", "dpps_jobs"),
                                      ("values", "normal"),
                                      ("graphs", "dout")])
def test_modules_are_found_by_name(sub, name):
    assert manifest.load_module(ROOT / "bench", sub, name) is \
        manifest.load_module(ROOT / "bench", sub, name)


def test_unknown_workload_is_an_error():
    with pytest.raises(KeyError):
        manifest.resolve("no-such-cell")
