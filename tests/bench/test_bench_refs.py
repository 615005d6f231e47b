"""The plain references agree with the program at a size a CPU test holds,
and their controls (one precision step down) fail the committed limits."""
import dataclasses
import json
import pathlib
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "bench"), str(ROOT / "src")]

import bench_tiny  # noqa: E402
from benchlib import compare, deploy, manifest, weights  # noqa: E402
from refs import dpps as ref_dpps  # noqa: E402
from refs import xlstm as ref_xlstm  # noqa: E402


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    path = bench_tiny.build(tmp_path_factory.mktemp("tiny"))
    return lambda w: manifest.resolve(w, path, path.parent / "bench")


@pytest.mark.parametrize("n", [4, 16])
def test_dout_weights_match_the_program(n):
    cell = manifest.resolve("consensus-paper-mlp")
    cfg = dict(cell.config, nodes=n)
    cell = dataclasses.replace(cell, config=cfg)
    np.testing.assert_array_equal(deploy.ref_weights(cell),
                                  deploy.topology(cfg).weight_matrix(0))


def test_mix_controls_lose_precision():
    x = jax.random.normal(jax.random.PRNGKey(0), (4, 1000))
    w = jnp.asarray(manifest.load_module(ROOT / "bench", "graphs",
                                         "dout").weights(4, 2))
    exact = np.asarray(w, np.float64) @ np.asarray(x, np.float64)
    err = {p: float(np.max(np.abs(np.asarray(ref_dpps.mix(w, x, p)) - exact)))
           for p in ("highest", "high", "default")}
    assert err["highest"] < 1e-6 < err["high"] < err["default"]


def test_xlstm_reference_matches_the_program_loss(tiny):
    cell = tiny("train-xlstm-148m")
    model = cell.config["model"]
    params = jax.tree_util.tree_map(
        lambda x: x[0], weights.xlstm_params(model, weights.seed_key(3), 1))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0,
                                model["vocab_size"])
    prog = cell.kind().xlstm_model(model).loss_fn(params, {"tokens": tokens})
    ref = ref_xlstm.loss(params, tokens, model=model)
    np.testing.assert_allclose(float(prog), float(ref), rtol=1e-5)


def test_dpps_reference_matches_the_program(tiny):
    from repro.api import Session

    cell = tiny("consensus-paper-mlp")
    cfg, kind = cell.config, cell.kind()
    jobs = kind.Jobs(cell, 11)
    ses = Session.build(deploy.topology(cfg), privacy=deploy.privacy(cfg),
                        schedule="dense", sync_interval=0, use_kernels=True,
                        chunk=10, packed=True)
    rep = ses.run(20, values=jobs.values(0), key=jobs.noise_key(0))
    y = np.asarray(kind.flat_rows(rep.state.push.s)
                   / rep.state.push.a[:, None])
    y_ref, _ = kind.ref_job(cell, jobs, 0)
    assert compare.y_gap(y, y_ref) < 1e-6


def test_leaf_gap_uses_the_median_floor():
    ref = {"a": 1.0, "b": 1.0, "tiny": 1e-9}
    prog = {"a": 1.0, "b": 1.01, "tiny": 2e-9}
    assert compare.leaf_gaps(prog, ref) == pytest.approx([0.0, 0.01, 1e-9])
    assert compare.median_gap(prog, ref) == pytest.approx(1e-9)
    assert compare.median_gap({**prog, "b": float("nan")}, ref) == float("inf")
    assert compare.moving_leaves(ref) == ["a", "b"]


def test_limits_are_committed_for_every_compared_number():
    for w in manifest.load_manifest()["workloads"]:
        cell = manifest.resolve(w["name"])
        want = ({"grad_gap", "change_gap"}
                if cell.traffic["kind"] == "partpsp_train" else {"y_gap"})
        assert set(cell.traffic["limits"]) == want
        assert all(0 < v <= 1 for v in cell.traffic["limits"].values())
        json.dumps(cell.traffic)


def test_consensus_control_fails_the_committed_limit():
    """The paper-mlp cell at its own size (16 x 7,850, 500 rounds): the
    reference with its mix in three bfloat16 passes reads above the limit."""
    cell = manifest.resolve("consensus-paper-mlp")
    kind = cell.kind()
    jobs = kind.Jobs(cell, 2**35 + 1)
    y, ans = kind.ref_job(cell, jobs, 0)
    y_c, ans_c = kind.ref_job(cell, jobs, 0, precision="high")
    gap = max(compare.y_gap(y_c, y), compare.y_gap(ans_c, ans))
    assert gap > cell.traffic["limits"]["y_gap"]


def test_training_control_fails_the_committed_limits(tiny):
    """The bfloat16 control of the training step, at the test's size, fails
    at least one of the committed limits."""
    cell = tiny("train-xlstm-148m")
    kind = cell.kind()
    clean = kind.ref_train(cell, 5)
    control = kind.ref_train(cell, 5, dtype="bfloat16", mix_precision="high")
    numbers = compare.train_numbers(
        *kind.train_observations(cell, control, clean))
    assert not compare.passed(compare.checks(numbers, cell.traffic["limits"]))
