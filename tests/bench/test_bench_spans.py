"""The span reduction (benchlib/spans.py): idle time split by overlap across
the innermost host span, idle inside a running program kept apart, the
fallback from repro.* to bench.* to untraced, the report spans' counts and
the model-scope join through jvp/transpose; on hand-built traces and on one
recorded on a TPU v5e. The accepted readers and phase join do not move."""
import contextlib
import gzip
import os
import pathlib
import subprocess
import sys
from types import SimpleNamespace as NS

import jax
import jax.numpy as jnp
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "bench"), str(ROOT / "src")]

from benchlib import device, manifest, spans, trace  # noqa: E402

RECORDED = ROOT / "bench" / "testdata" / "consensus_v5e.xplane.pb"
RECORDED_HLO = ROOT / "bench" / "testdata" / "consensus_v5e.hlo.txt.gz"


def ev(name, start, end, **stats):
    return NS(name=name, start_ns=float(start),
              duration_ns=float(end - start), stats=list(stats.items()))


def profile():
    """A window [0, 1000) with one job; the chip busy 300 ns of it."""
    host = NS(name="/host:CPU", lines=[NS(name="python", events=[
        ev("bench.window", 0, 1000),
        ev("bench.job", 0, 700),
        ev("repro.api.run", 50, 650, call=1),
        ev("repro.api.state_init", 60, 100, call=1),
        ev("repro.engine.dispatch", 100, 120, call=1, t0=0, rounds=5),
        ev("repro.api.report", 400, 640, call=1, dispatches=2,
           host_syncs=3, compiles=0),
        ev("repro.api.wait", 410, 500, call=1),
        ev("bench.readback", 650, 700),
        ev("other", 0, 1000),
    ])])
    ops = [ev("%fusion.1 = f32[8]{0} fusion(%p)", 130, 200),
           ev("%fusion.2 = f32[8]{0} fusion(%p)", 250, 380),
           ev("%copy.3 = f32[8]{0} copy(%q)", 800, 900)]
    modules = [ev("jit_run(1)", 120, 380), ev("jit_other(2)", 800, 900)]
    dev = NS(name="/device:TPU:0", lines=[NS(name="XLA Ops", events=ops),
                                          NS(name="XLA Modules",
                                             events=modules)])
    return NS(planes=[host, dev])


# Idle: [0, 130) [200, 250) [380, 800) [900, 1000), 700 ns in all.
EXPECTED_NS = {
    "bench.job": 50,                    # [0, 50): no program span yet
    "repro.api.run": 10 + 20 + 10,      # [50, 60) [380, 400) [640, 650)
    "repro.api.state_init": 40,
    "repro.engine.dispatch": 20,        # [100, 120); [120, 130) is a module's
    "repro.api.report": 10 + 140,       # [400, 410) [500, 640)
    "repro.api.wait": 90,
    "bench.readback": 50,
    "untraced": 100 + 100,              # [700, 800) [900, 1000)
    "in_program": 10 + 50,              # [120, 130) [200, 250) in jit_run
}


def test_idle_is_split_by_overlap_across_the_innermost_span():
    got = spans.idle_by_span(profile(), n_devices=1)
    assert got == pytest.approx({k: v * 1e-9 for k, v in EXPECTED_NS.items()})
    s = trace.reduce(profile(), n_devices=1)
    assert sum(got.values()) == pytest.approx(s.window_s - s.busy_s)


def test_a_gap_is_not_given_whole_to_the_span_at_its_midpoint():
    """[380, 800) has its midpoint in repro.api.report, yet four spans and
    the untraced tail share it by how much of it each covers."""
    got = spans.idle_by_span(profile(), n_devices=1)
    assert got["repro.api.report"] == pytest.approx(150e-9)
    assert got["untraced"] == pytest.approx(200e-9)


def test_idle_inside_a_running_program_is_its_own_bucket():
    p = profile()
    p.planes[1].lines[1].events = []        # no XLA Modules line events
    got = spans.idle_by_span(p, n_devices=1)
    assert "in_program" not in got
    assert got["repro.engine.dispatch"] == pytest.approx(20e-9)
    # [120, 130) and [200, 250) now count as host time under the call
    assert got["repro.api.run"] == pytest.approx((40 + 10 + 50) * 1e-9)


def test_without_program_spans_the_bench_spans_name_the_idle():
    p = profile()
    p.planes[0].lines[0].events = [e for e in p.planes[0].lines[0].events
                                   if not e.name.startswith("repro.")]
    got = spans.idle_by_span(p, n_devices=1)
    assert set(got) == {"bench.job", "bench.readback", "untraced",
                        "in_program"}
    assert got["bench.job"] == pytest.approx((120 + 270) * 1e-9)


def test_longer_than_keeps_only_the_long_gaps():
    """Idle intervals: [0, 130) 130, [200, 250) 50, [380, 800) 420,
    [900, 1000) 100; above 120 ns only the first and the third stay."""
    got = spans.idle_by_span(profile(), n_devices=1, longer_than=120e-9)
    assert sum(got.values()) == pytest.approx(550e-9)
    assert got["in_program"] == pytest.approx(10e-9)
    assert got["untraced"] == pytest.approx(100e-9)


def test_a_trace_without_a_window_is_refused():
    p = profile()
    p.planes[0].lines[0].events.pop(0)
    with pytest.raises(ValueError):
        spans.idle_by_span(p, n_devices=1)


def test_chips_are_averaged():
    p = profile()
    p.planes.append(NS(name="/device:TPU:1", lines=[]))   # idle throughout
    got = spans.idle_by_span(p, n_devices=2)
    assert sum(got.values()) == pytest.approx((700 + 1000) / 2 * 1e-9)


def test_report_counts_sum_the_report_spans():
    assert spans.report_counts(profile()) == {
        "reports": 1.0, "dispatches": 2.0, "host_syncs": 3.0,
        "compiles": 0.0}


@pytest.mark.parametrize("path,want", [
    ("jit(f)/partpsp_local_grads/transpose(jvp(model_mlstm))/while/mul",
     "model_mlstm"),
    ("jit(f)/partpsp_shared_grads/jvp()/while/body/model_slstm/dot",
     "model_slstm"),
    ("jit(f)/jvp(model_embed)/gather", "model_embed"),
    ("jit(f)/partpsp_local_grads/transpose(jvp(vmap(model_head)))/dot",
     "model_head"),
    ("jit(f)/partpsp_shared_grads/while/body/transpose;model_slstm/mul",
     "model_slstm"),
    ("jit(f)/model_head/model_attn/add", "model_head"),     # outermost
    ("jit(f)/model_mlstm_extra/add", None),                 # whole names
    ("jit(f)/dpps_gossip/pushsum_mix/dot", None),
])
def test_model_phase_of_path_unwraps_transformations(path, want):
    assert spans.model_phase_of_path(path) == want


def test_model_scopes_leave_the_accepted_phase_join_alone():
    assert trace.phase_of_path(
        "jit(f)/partpsp_local_grads/transpose(jvp(model_mlstm))/while/x"
    ) == "partpsp_local_grads"
    assert trace.phase_of_path("jit(f)/jvp(model_embed)/gather") is None


MODEL_HLO = """HloModule jit_step, entry_computation_layout={()->f32[8]}
  %fusion.1 = f32[8]{0} fusion(%p), metadata={op_name="jit(step)/partpsp_local_grads/jvp()/while/body/model_mlstm/dot"}
  %fusion.2 = f32[8]{0} fusion(%p), metadata={op_name="jit(step)/partpsp_shared_grads/transpose(jvp(model_mlstm))/mul"}
  %fusion.3 = f32[8]{0} fusion(%p), metadata={op_name="jit(step)/partpsp_local_grads/transpose(jvp(model_head))/add"}
  ROOT %fusion.4 = f32[8]{0} fusion(%q), metadata={op_name="jit(step)/dpps_gossip/pushsum_mix/dot"}
"""


def test_model_phase_s_joins_the_window_program_ops():
    host = NS(name="/host:CPU", lines=[NS(name="python", events=[
        ev("bench.window", 0, 1000)])])
    ops = [ev("%while.9 = (f32[8]) while(%p)", 100, 400),   # holds its body
           ev("%fusion.1 = f32[8]{0} fusion(%p)", 100, 200),
           ev("%fusion.2 = f32[8]{0} fusion(%p)", 200, 260),
           ev("%fusion.3 = f32[8]{0} fusion(%p)", 260, 300),
           ev("%fusion.4 = f32[8]{0} fusion(%p)", 300, 400),
           ev("%fusion.1 = f32[8]{0} fusion(%x)", 500, 600),  # other module
           ev("%fusion.2 = f32[8]{0} fusion(%p)", 950, 1100)]  # clipped
    modules = [ev("jit_step(1)", 100, 400), ev("jit_other(2)", 500, 600),
               ev("jit_step(1)", 950, 1100)]
    dev = NS(name="/device:TPU:0", lines=[NS(name="XLA Ops", events=ops),
                                          NS(name="XLA Modules",
                                             events=modules)])
    got = spans.model_phase_s(NS(planes=[host, dev]), n_devices=1,
                              hlo_texts=(MODEL_HLO,))
    assert got == pytest.approx({"model_mlstm": (100 + 60 + 50) * 1e-9,
                                 "model_head": 40e-9})


def _tiny_xlstm_grad_hlo(scoped: bool, monkeypatch) -> str:
    """A tiny xLSTM Transformer's gradient inside PartPSP's local-gradient
    phase, with or without the model's block scopes."""
    from repro.models import Transformer
    from repro.models import transformer as tf
    from repro.models.config import ModelConfig, XLSTMGroup
    from repro.obs import phase

    if not scoped:
        monkeypatch.setattr(tf, "phase", lambda n: contextlib.nullcontext())
    model = Transformer(ModelConfig(
        name="tiny", d_model=16, vocab_size=32, n_heads=2, n_kv_heads=2,
        head_dim=16, d_ff=0, tie_embedding=True,
        groups=(XLSTMGroup(n_units=1, mlstm_per_unit=1, proj_factor=2.0),)))
    params = model.init(jax.random.PRNGKey(0))

    def grads(p, tokens):
        with phase("partpsp_local_grads"):
            return jax.grad(model.loss_fn)(p, {"tokens": tokens})

    return jax.jit(grads).lower(
        params, jnp.zeros((2, 6), jnp.int32)).compile().as_text()


def test_model_scopes_move_no_op_out_of_its_accepted_phase(monkeypatch):
    scoped = _tiny_xlstm_grad_hlo(True, monkeypatch)
    bare = _tiny_xlstm_grad_hlo(False, monkeypatch)
    assert trace.hlo_phase_map(scoped) == trace.hlo_phase_map(bare)
    assert set(trace.hlo_phase_map(scoped).values()) == {
        "partpsp_local_grads"}
    assert set(spans.model_phase_map(scoped).values()) >= {
        "model_embed", "model_mlstm", "model_slstm", "model_head"}
    assert spans.model_phase_map(bare) == {}


def test_the_partpsp_segment_names_every_xlstm_block():
    """In the training cell's own program — the node-vmapped PartPSP
    segment — every block of a tiny xLSTM is found, forward and backward,
    though vmap and jvp wrap the embedding's and the head's scopes."""
    from repro.api import PrivacySpec, Session
    from repro.core.topology import DOutGraph
    from repro.models import Transformer
    from repro.models.config import ModelConfig, XLSTMGroup

    model = Transformer(ModelConfig(
        name="tiny", d_model=16, vocab_size=32, n_heads=2, n_kv_heads=2,
        head_dim=16, d_ff=0, tie_embedding=True,
        groups=(XLSTMGroup(n_units=1, mlstm_per_unit=1, proj_factor=2.0),)))
    session = Session.build(
        DOutGraph(n_nodes=4, d=2), privacy=PrivacySpec(b=3.0, gamma_n=1e-9),
        model=model, partition=((r".*mlstm.*", "shared"),), chunk=2,
        sync_interval=5)
    hlo = session.segment_runner(()).lower(
        session.train_state(), {"tokens": jnp.zeros((2, 4, 2, 6), jnp.int32)},
        jax.random.PRNGKey(0)).compile().as_text()
    found = spans.model_phase_map(hlo)
    assert set(found.values()) == {"model_embed", "model_mlstm",
                                   "model_slstm", "model_head"}


@pytest.fixture(scope="module")
def recorded():
    from jax.profiler import ProfileData

    hlo = gzip.decompress(RECORDED_HLO.read_bytes()).decode()
    return ProfileData.from_file(str(RECORDED)), hlo


def test_recorded_v5e_idle_split(recorded):
    """A 40-round DPPS job traced on one TPU v5e before the program had
    spans: the buckets are the bench spans, untraced and in_program, and
    sum to the window's idle time."""
    prof, hlo = recorded
    got = spans.idle_by_span(prof, n_devices=1)
    s = trace.reduce(prof, n_devices=1, hlo_texts=(hlo,))
    assert sum(got.values()) == pytest.approx(s.window_s - s.busy_s,
                                              rel=1e-9)
    assert set(got) <= {"bench.job", "bench.readback", "untraced",
                        "in_program"}
    assert got["bench.job"] > 0 and got["in_program"] > 0
    assert spans.report_counts(prof) == {"reports": 0.0}
    assert spans.model_phase_s(prof, n_devices=1, hlo_texts=(hlo,)) == {}


PER_LAYER = [m["name"] for m in manifest.load_manifest()["per_layer"]]


@pytest.mark.parametrize("metric", PER_LAYER)
def test_accepted_readers_ignore_the_profile_in_their_view(metric, recorded):
    """Every accepted reader gives the same value on the recorded trace
    whether or not its view also carries the profile and the HLO texts."""
    prof, hlo = recorded
    s = trace.reduce(prof, n_devices=1, hlo_texts=(hlo,))
    (entry,) = [m for m in manifest.load_manifest()["per_layer"]
                if m["name"] == metric]
    cell = manifest.resolve(entry["workloads"][0])
    view = {"summary": s, "cell": cell,
            "peaks": device.peaks("TPU v5 lite"), "window_s": s.window_s,
            "rounds": 40, "steps": 2}
    read = cell.reader(metric)
    assert read(view) == read({**view, "profile": prof,
                               "hlo_texts": (hlo,)})
    if entry["workloads"][0] == "consensus-paper-mlp":
        assert read(view) is not None


def test_span_table_refuses_without_a_tpu():
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, str(ROOT / "bench" / "span_table.py"),
         "--workload", "consensus-paper-mlp", "--seed", "3000000001"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode == 2
    assert "{" not in proc.stdout and "TPU" in proc.stderr
