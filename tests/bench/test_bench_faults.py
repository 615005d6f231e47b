"""The comparison that decides ``correct``, end to end on the CPU.

Each test drives a whole run of a cell (the look for a chip skipped, the
sizes cut to what a test holds, the limits as committed) with the program's
timed path broken underneath, and sees ``correct`` come out false; the
unbroken run comes out true.
"""
import pathlib
import sys
import time

import jax
import jax.numpy as jnp
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "bench"), str(ROOT / "src")]

import bench_tiny  # noqa: E402
from benchlib import cli  # noqa: E402

import repro.api.session as session_mod  # noqa: E402
import repro.core.dpps as dpps_mod  # noqa: E402
import repro.core.partpsp as partpsp_mod  # noqa: E402
import repro.engine.rounds as rounds_mod  # noqa: E402


@pytest.fixture(scope="module")
def manifest(tmp_path_factory):
    return bench_tiny.build(tmp_path_factory.mktemp("tiny"))


def run(manifest, workload, seed=2**40 + 7):
    args = cli.parse(["--workload", workload, "--seed", str(seed),
                      "--seconds", "0.5", "--trace", "0",
                      "--manifest", str(manifest)])
    return cli.run_cell(args, time.perf_counter(), require_tpu=False)


def unchanged_partpsp(state, *a, **kw):
    _, metrics = ORIG["partpsp_step"](state, *a, **kw)
    return state, metrics


def unchanged_dpps(state, *a, **kw):
    _, diag = ORIG["dpps_step"](state, *a, **kw)
    return state, diag


def half_batch_grads(loss_fn, params, batch, keys):
    half = jax.tree_util.tree_map(lambda x: x[:, : x.shape[1] // 2], batch)
    return ORIG["_node_grads"](loss_fn, params, half, keys)


def no_exchange(state, **kw):
    return state


def altered_answer(self, state):
    out = ORIG["consensus"](self, state)
    first = out[0]
    bump = 0.01 * jnp.max(jnp.abs(first))
    return [first.at[(0,) * first.ndim].add(bump)] + list(out[1:])


ORIG = {"partpsp_step": rounds_mod.partpsp_step,
        "dpps_step": rounds_mod.dpps_step,
        "_node_grads": partpsp_mod._node_grads,
        "consensus": session_mod.ProtocolSession.consensus}

TRAIN_FAULTS = {
    "unchanged": (rounds_mod, "partpsp_step", unchanged_partpsp),
    "half_batch": (partpsp_mod, "_node_grads", half_batch_grads),
    "no_exchange": (dpps_mod, "gossip_packed", no_exchange),
}
CONSENSUS_FAULTS = {
    "unchanged": (rounds_mod, "dpps_step", unchanged_dpps),
    "no_exchange": (dpps_mod, "gossip_packed", no_exchange),
    "answer_altered": (session_mod.ProtocolSession, "consensus",
                       altered_answer),
}


@pytest.mark.parametrize("workload", ["train-xlstm-148m",
                                      "consensus-paper-mlp"])
def test_sound_run_is_correct(manifest, workload):
    result = run(manifest, workload)
    assert result["correct"], result["checks"]
    assert result["attempted"] > 0 and result["failed"] == 0
    assert list(result)[-1] == "checks"


@pytest.mark.parametrize("fault", sorted(TRAIN_FAULTS))
def test_training_fault_is_caught(manifest, monkeypatch, fault):
    monkeypatch.setattr(*TRAIN_FAULTS[fault])
    result = run(manifest, "train-xlstm-148m")
    assert not result["correct"], result["checks"]


@pytest.mark.parametrize("fault", sorted(CONSENSUS_FAULTS))
def test_consensus_fault_is_caught(manifest, monkeypatch, fault):
    monkeypatch.setattr(*CONSENSUS_FAULTS[fault])
    result = run(manifest, "consensus-paper-mlp")
    assert not result["correct"], result["checks"]
