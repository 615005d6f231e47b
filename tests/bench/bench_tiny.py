"""A manifest of the benchmark's own cells cut to a size a CPU test holds:
the same configurations, mixes, readers and limits, smaller sizes."""
import json
import pathlib
import shutil

ROOT = pathlib.Path(__file__).resolve().parents[2]


def build(where: pathlib.Path) -> pathlib.Path:
    """Write the tiny manifest and its files under ``where``; returns the
    manifest path."""
    bench = where / "bench"
    for sub in ("metrics", "kinds", "values", "graphs"):
        shutil.copytree(ROOT / "bench" / sub, bench / sub,
                        ignore=shutil.ignore_patterns("__pycache__"))
    (bench / "configs").mkdir()
    (bench / "traffic").mkdir()
    m = json.loads((ROOT / "BENCHMARK.json").read_text())

    def load(sub, name):
        return json.loads((ROOT / "bench" / sub / f"{name}.json").read_text())

    def save(sub, name, obj):
        (bench / sub / f"{name}.json").write_text(json.dumps(obj))

    x = load("configs", "xlstm-148m")
    x["model"].update(d_model=64, vocab_size=128, n_heads=4, n_units=1,
                      mlstm_per_unit=1)
    x["context_length"] = 8
    save("configs", "xlstm-148m", x)
    p = load("configs", "paper-mlp-n16")
    p.update(nodes=4, values={"kind": "normal", "leaves": [[8, 4], [4]]},
             d_s=36, d_pad=128)
    save("configs", "paper-mlp-n16", p)
    for w in m["workloads"]:
        t = load("traffic", w["traffic"])
        if t["kind"] == "partpsp_train":
            t.update(trace_seconds=1)
        else:
            t.update(rounds=min(t["rounds"], 20), segment=10, check_jobs=2)
        save("traffic", w["traffic"], t)
    path = where / "BENCHMARK.json"
    path.write_text(json.dumps(m))
    return path
