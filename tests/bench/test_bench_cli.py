"""The command refuses to run without a TPU, and its inputs follow the seed."""
import os
import pathlib
import shutil
import subprocess
import sys

import jax
import numpy as np

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "bench"), str(ROOT / "src")]

from benchlib import manifest, weights  # noqa: E402
from benchlib.data import TokenStream, make_batcher  # noqa: E402

ARGS = ["--workload", "consensus-paper-mlp", "--seed", "3000000001",
        "--seconds", "1", "--trace", "0"]


def _run(cwd: pathlib.Path, script: pathlib.Path):
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run([sys.executable, str(script)] + ARGS, cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=300)


def test_no_tpu_exits_nonzero_without_a_result():
    proc = _run(ROOT, ROOT / "bench" / "run.py")
    assert proc.returncode != 0
    assert "{" not in proc.stdout
    assert "TPU" in proc.stderr


def test_benchmark_files_alone_do_not_run(tmp_path):
    for p in manifest.load_manifest()["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    proc = _run(tmp_path, tmp_path / "bench" / "run.py")
    assert proc.returncode != 0
    assert "{" not in proc.stdout


def test_large_seeds_make_keys():
    a = np.asarray(jax.random.key_data(weights.seed_key(2**31 + 5)))
    b = np.asarray(jax.random.key_data(weights.seed_key(2**33 + 5)))
    assert not np.array_equal(a, b)


def test_token_stream_follows_the_seed():
    stream = TokenStream(vocab_size=64, seq_len=8, n_nodes=4,
                         per_node_batch=2)

    def tokens(seed, t):
        return np.asarray(make_batcher(stream, weights.seed_key(seed))(t))

    assert tokens(5, 0).shape == (4, 2, 8)
    np.testing.assert_array_equal(tokens(5, 3), tokens(5, 3))
    assert not np.array_equal(tokens(5, 3), tokens(6, 3))
    assert not np.array_equal(tokens(5, 3), tokens(5, 4))


def test_job_values_follow_the_seed():
    cell = manifest.resolve("consensus-paper-mlp")
    kind = cell.kind()

    def values(seed, j):
        rows = kind.flat_rows(kind.Jobs(cell, seed).values(j))
        return np.asarray(rows)

    assert values(9, 0).shape == (16, 7850)
    np.testing.assert_array_equal(values(9, 2), values(9, 2))
    assert not np.array_equal(values(9, 2), values(10, 2))
    assert not np.array_equal(values(9, 2), values(9, 3))


def test_xlstm_weights_follow_the_seed():
    model = dict(manifest.resolve("train-xlstm-148m").config["model"],
                 d_model=32, vocab_size=64, n_units=1, mlstm_per_unit=1)
    a = weights.xlstm_params(model, weights.seed_key(1), 2)
    b = weights.xlstm_params(model, weights.seed_key(1), 2)
    c = weights.xlstm_params(model, weights.seed_key(2), 2)
    np.testing.assert_array_equal(a["embed"], b["embed"])
    assert not np.array_equal(a["embed"], c["embed"])
    np.testing.assert_array_equal(a["embed"][0], a["embed"][1])
