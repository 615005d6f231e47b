"""The train-zamba2-7b-tp2 cell at a size a CPU test holds: its sizes, and
whole runs of the cell's kind, sound and with faults planted
(``test_bench_zamba2_refs.py`` checks its reference)."""
import json
import pathlib
import shutil
import sys
import time

import jax
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "bench"), str(ROOT / "src"), str(ROOT / "tests/bench")]

import zamba2_tiny  # noqa: E402
from benchlib import cli, counts, zamba2_params  # noqa: E402

CELL = "train-zamba2-7b-tp2"
CFG, KIND = zamba2_tiny.CFG, zamba2_tiny.KIND


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    """A manifest whose zamba2 cell runs the tiny configuration."""
    where = tmp_path_factory.mktemp("tiny-zamba2")
    bench = where / "bench"
    for sub in ("metrics", "kinds", "values", "graphs", "traffic", "configs"):
        shutil.copytree(ROOT / "bench" / sub, bench / sub,
                        ignore=shutil.ignore_patterns("__pycache__"))
    (bench / "configs" / "zamba2-7b-tp2.json").write_text(
        json.dumps(zamba2_tiny.config()))
    t = json.loads((bench / "traffic" / "train_s4096_b1.json").read_text())
    t.update(trace_seconds=1, steps_ahead=3)
    (bench / "traffic" / "train_s4096_b1.json").write_text(json.dumps(t))
    path = where / "BENCHMARK.json"
    shutil.copy(ROOT / "BENCHMARK.json", path)
    return path


def run(path, seed=2**40 + 11):
    args = cli.parse(["--workload", CELL, "--seed", str(seed), "--seconds",
                      "0.5", "--trace", "0", "--manifest", str(path)])
    return cli.run_cell(args, time.perf_counter(), require_tpu=False)


def test_sizes_match_the_configuration():
    m = zamba2_params.held(CFG)
    shapes = zamba2_params.leaf_shapes(m)
    shared = sum(counts.size(s) for p, s in shapes.items()
                 if p.startswith("group_0/shared_blocks/"))
    total = sum(counts.size(s) for s in shapes.values())
    assert shared == CFG["d_s"] == CFG["d_pad"] == 166_996_480
    assert total - shared == CFG["d_l"] == 347_030_040
    assert total == CFG["n_params"] == 514_026_520
    assert CFG["d_pad"] % 128 == 0


def test_shapes_match_the_program():
    from repro.core.partition import _path_str

    model = KIND.zamba2_model(CFG)
    tree = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    prog = {_path_str(p): tuple(x.shape)
            for p, x in jax.tree_util.tree_flatten_with_path(tree)[0]}
    ours = zamba2_params.leaf_shapes(zamba2_params.held(CFG))
    assert list(prog) == list(ours)
    assert prog == ours


def test_whole_layers_count_the_published_parameters():
    """The whole (share size 1) seven layers hold what transformers'
    Zamba2ForCausalLM holds in its layers 0-6 with one tied block."""
    m = zamba2_params.held(dict(CFG, share=dict(CFG["share"], size=1),
                                n_mamba_heads=112, mamba_ngroups=2,
                                num_attention_heads=32, vocab_size=32000))
    total = sum(counts.size(s)
                for s in zamba2_params.leaf_shapes(m).values())
    assert total == CFG["whole_layers_params"] == 1_014_709_808


def test_tokens_drawn_ahead_are_the_stream_s():
    """The window's tokens drawn in set-up, in one call, are the tokens
    ``make_batcher`` gives step by step (the reference reads those)."""
    from benchlib.data import make_batcher
    from benchlib.manifest import Cell

    cell = Cell(name=CELL, chips=1, config=zamba2_tiny.config(),
                traffic={"per_node_batch": 1,
                         "stream": {"markov_rank": 8, "node_skew": 0.5}},
                end_to_end=(), per_layer=(), bench_dir=ROOT / "bench")
    key = jax.random.PRNGKey(4)
    ahead = KIND.batcher(cell, key, 3)
    one = make_batcher(KIND.stream(cell), key)
    for t in range(5):
        assert (ahead(t) == one(t)).all(), t


def test_sound_run_is_correct(tiny):
    result = run(tiny)
    assert result["correct"], result["checks"]
    assert result["attempted"] > 0 and result["failed"] == 0
    assert set(result["checks"]) == {"y_gap", "window_compiles"}


def _unchanged(orig):
    def step(state, *a, **kw):
        _, metrics = orig(state, *a, **kw)
        return state, metrics
    return step


def _no_exchange(state, **kw):
    return state


def _half_sequence(orig):
    def grads(loss_fn, params, batch, keys):
        half = jax.tree_util.tree_map(lambda x: x[..., : x.shape[-1] // 2],
                                      batch)
        return orig(loss_fn, params, half, keys)
    return grads


@pytest.mark.parametrize("fault", ["unchanged", "no_exchange",
                                   "half_sequence"])
def test_fault_is_caught(tiny, monkeypatch, fault):
    """The program's step broken underneath: the state left unchanged, the
    exchange left out, each node's gradients taken over the first half of
    its sequence (its one sequence is its whole batch)."""
    import repro.core.dpps as dpps_mod
    import repro.core.partpsp as partpsp_mod
    import repro.engine.rounds as rounds_mod

    if fault == "unchanged":
        monkeypatch.setattr(rounds_mod, "partpsp_step",
                            _unchanged(rounds_mod.partpsp_step))
    elif fault == "no_exchange":
        monkeypatch.setattr(dpps_mod, "gossip_packed", _no_exchange)
    else:
        monkeypatch.setattr(partpsp_mod, "_node_grads",
                            _half_sequence(partpsp_mod._node_grads))
    result = run(tiny)
    assert not result["correct"], result["checks"]
