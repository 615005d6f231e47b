"""Host spans and counts inside Session and the engine: every call opens
the span tree of repro.obs.trace under one ``call`` id, RunReport.counts
counts its dispatches, host syncs, trajectory leaves read back and
compiles, a call that compiled nothing makes one host sync (the batched
trajectory readback, which equals reading each segment's leaves back),
and Session.profile's join reads the trace through
jax.profiler.ProfileData (also a recorded TPU v5e trace)."""
import glob
import gzip
import math
import pathlib
import shutil

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.api import BudgetHook, MetricsHook, PrivacySpec, Session
from repro.core.topology import DOutGraph, calibrate_constants
from repro.obs import MetricsBus
from repro.obs.trace import (
    HOST_SPANS,
    KNOWN_PHASES,
    SPAN_API_CONSENSUS,
    SPAN_API_COPY_STATE,
    SPAN_API_HOOKS,
    SPAN_API_REPORT,
    SPAN_API_RUN,
    SPAN_API_STATE_INIT,
    SPAN_API_TRAIN,
    SPAN_API_WAIT,
    SPAN_ENGINE_DISPATCH,
    SPAN_ENGINE_INPUTS,
    compile_count,
    phase_breakdown,
    span,
    xplane_durations,
)

ROOT = pathlib.Path(__file__).resolve().parents[1]
RECORDED = ROOT / "bench" / "testdata" / "consensus_v5e.xplane.pb"
RECORDED_HLO = ROOT / "bench" / "testdata" / "consensus_v5e.hlo.txt.gz"

N, T = 4, 7
TOPO = DOutGraph(n_nodes=N, d=2)
CP, LAM = calibrate_constants(TOPO)


def _values():
    key = jax.random.PRNGKey(0)
    return [jax.random.normal(key, (N, 5)),
            jax.random.normal(jax.random.fold_in(key, 1), (N, 2, 3))]


def _session(chunk=3, **kw):
    return Session.build(TOPO, privacy=PrivacySpec(b=5.0, gamma_n=0.02,
                                                   c_prime=CP, lam=LAM),
                         sync_interval=3, chunk=chunk, **kw)


def _train_session(chunk=3):
    params = {"l1": jnp.ones((6, 4)) / 3.0, "l2": jnp.ones((4, 3)) / 3.0}

    def loss_fn(p, batch, k):
        x, y = batch
        logp = jax.nn.log_softmax(jnp.tanh(x @ p["l1"]) @ p["l2"])
        return -jnp.mean(jnp.take_along_axis(logp, y[:, None], axis=1))

    session = Session.build(
        TOPO, model=loss_fn, partition=(("l1", "shared"),), params=params,
        privacy=PrivacySpec(b=5.0, gamma_n=1e-4, c_prime=CP, lam=LAM),
        sync_interval=3, chunk=chunk)
    key = jax.random.PRNGKey(5)
    x = jax.random.normal(key, (T, N, 2, 6))
    y = jax.random.randint(jax.random.fold_in(key, 1), (T, N, 2), 0, 3)
    return session, lambda t: (x[t], y[t])


def _traced(tmp_path, fn):
    """Run ``fn`` under the profiler; its result and the repro.* spans
    the trace holds, as (name, start, end, stats), by start."""
    with jax.profiler.trace(str(tmp_path)):
        out = fn()
    path = sorted(glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                            recursive=True))[-1]
    spans = [(e.name, e.start_ns, e.start_ns + e.duration_ns,
              dict(e.stats))
             for plane in ProfileData.from_file(path).planes
             for line in plane.lines for e in line.events
             if e.name.startswith("repro.")]
    return out, sorted(spans, key=lambda s: s[1])


def _named(spans, name):
    return [s for s in spans if s[0] == name]


def test_run_opens_the_span_tree_under_one_call_id(tmp_path):
    session = _session()

    def calls():
        first = session.run(T, values=_values())
        second = session.run(T, values=_values())
        return first, second, session.consensus(second.state)

    (first, second, _), spans = _traced(tmp_path, calls)
    runs = _named(spans, SPAN_API_RUN)
    assert [s[3]["call"] for s in runs] == [1, 2]
    segments = math.ceil(T / 3)
    leaves = len(second.trajectory)
    for (_, a, b, stats), report in zip(runs, (first, second)):
        inside = [s for s in spans
                  if a <= s[1] and s[2] <= b and s[0] != SPAN_API_RUN]
        assert {s[3]["call"] for s in inside} == {stats["call"]}
        names = [s[0] for s in inside]
        assert names.count(SPAN_API_STATE_INIT) == 1
        assert names.count(SPAN_API_COPY_STATE) == 1
        assert names.count(SPAN_ENGINE_INPUTS) == 0     # no eps_at
        assert names.count(SPAN_API_HOOKS) == 0         # no hooks
        dispatch = _named(inside, SPAN_ENGINE_DISPATCH)
        assert [(s[3]["t0"], s[3]["rounds"]) for s in dispatch] == [
            (0, 3), (3, 3), (6, 1)]
        assert names.count(SPAN_API_WAIT) == report.counts["host_syncs"]
        (rep,) = _named(inside, SPAN_API_REPORT)
        assert {k: rep[3][k] for k in report.counts} == report.counts
        assert report.counts["dispatches"] == segments
    # The first call compiled and synced its first segment; the second
    # compiled nothing, so it only reads the trajectory back, every
    # segment's leaves in one batched sync.
    assert first.counts["host_syncs"] == 1 + 1
    assert second.counts["host_syncs"] == 1
    for report in (first, second):
        assert report.counts["readback_leaves"] == segments * leaves
    (consensus,) = _named(spans, SPAN_API_CONSENSUS)
    assert consensus[3]["call"] == 2 and consensus[1] >= runs[1][2]
    assert set(HOST_SPANS) >= {s[0] for s in spans}


def test_train_opens_the_span_tree_under_one_call_id(tmp_path):
    session, batch_at = _train_session()
    bus = MetricsBus()
    hook = MetricsHook(log_every=10**9, print_fn=lambda s: None, bus=bus)
    report, spans = _traced(tmp_path, lambda: session.train(
        T, batch_at, hooks=[hook]))
    (train,) = _named(spans, SPAN_API_TRAIN)
    assert {s[3]["call"] for s in spans} == {1}
    assert all(train[1] <= s[1] and s[2] <= train[2] for s in spans)
    inputs = _named(spans, SPAN_ENGINE_INPUTS)
    dispatch = _named(spans, SPAN_ENGINE_DISPATCH)
    assert [s[3]["t0"] for s in inputs] == [s[3]["t0"] for s in dispatch] \
        == [0, 3, 6]
    # inputs are stacked before their segment is enqueued
    assert all(i[2] <= d[1] for i, d in zip(inputs, dispatch))
    assert len(_named(spans, SPAN_API_STATE_INIT)) == 1
    # prepare, a consume per segment, finish, finish_run
    assert len(_named(spans, SPAN_API_HOOKS)) == 1 + 3 + 1 + 1
    assert len(_named(spans, SPAN_API_WAIT)) == report.counts["host_syncs"]
    assert report.counts["dispatches"] == 3 and report.counts["compiles"] > 0
    gauges = bus.snapshot()["gauges"]
    for name, value in report.counts.items():
        assert gauges[f"run.{name}"] == value


def test_run_with_eps_at_stacks_inputs_in_a_span(tmp_path):
    session = _session()
    values = _values()
    eps_at = lambda t: [jnp.zeros_like(x) for x in values]  # noqa: E731
    report, spans = _traced(tmp_path, lambda: session.run(
        T, values=values, eps_at=eps_at))
    inputs = _named(spans, SPAN_ENGINE_INPUTS)
    assert [(s[3]["t0"], s[3]["rounds"]) for s in inputs] == [
        (0, 3), (3, 3), (6, 1)]
    assert report.counts["dispatches"] == 3


@pytest.mark.parametrize("rounds,chunk", [(7, 3), (6, 3), (1, 4), (8, 8)])
def test_dispatches_count_the_segments(rounds, chunk):
    report = _session(chunk=chunk).run(rounds, values=_values())
    assert report.counts["dispatches"] == math.ceil(rounds / chunk)
    assert report.counts["host_syncs"] == 1 + 1
    assert report.counts["readback_leaves"] == (
        report.counts["dispatches"] * len(report.trajectory))


def test_loop_driver_counts_one_dispatch_a_round():
    session, batch_at = _train_session()
    report = session.train(4, batch_at, driver="loop")
    assert report.counts["dispatches"] == 4


def test_a_call_that_compiles_nothing_makes_no_instrumentation_sync():
    session = _session()
    first = session.run(T, values=_values())
    second = session.run(T, values=_values())
    assert first.counts["compiles"] > 0 and first.compile_s > 0.0
    assert second.counts["compiles"] == 0
    assert second.compile_s == 0.0 and second.run_s > 0.0
    assert first.counts["host_syncs"] == 1 + 1
    assert second.counts["host_syncs"] == 1
    assert second.summary()["counts"] == second.counts


def test_span_hooks_sync_every_segment_and_see_no_compile_twice():
    from repro.obs import TimelineHook

    session = _session()
    hook = TimelineHook(bus=MetricsBus())
    first = session.run(T, values=_values(), hooks=[hook])
    second = session.run(T, values=_values(), hooks=[hook])
    segments = math.ceil(T / 3)
    assert first.compile_s > 0.0 and second.compile_s == 0.0
    assert second.counts["host_syncs"] == segments + 1
    assert second.counts["readback_leaves"] == (
        segments * len(second.trajectory))
    spans = [e["name"] for e in hook.timeline.to_chrome_trace()[
        "traceEvents"] if e.get("cat") == "segment" and e["tid"] == 1]
    assert spans == ["trace/compile+execute"] + ["execute"] * (
        2 * segments - 1)


def _train(rounds, **kw):
    session, batch_at = _train_session()
    return session.train(rounds, batch_at, **kw)


def _strict_budget_run():
    session = _session()
    hook = BudgetHook(1.5 * session.cfg.epsilon_per_round, strict=True,
                      warn=lambda s: None)
    report = session.run(T, values=_values(), hooks=[hook])
    assert report.aborted and 0 < report.rounds < T
    return report


@pytest.mark.parametrize("call", [
    lambda: _session().run(T, values=_values()),
    lambda: _session().run(T, values=_values(), eps_at=lambda t: [
        jnp.full_like(x, 0.01 * (t + 1)) for x in _values()]),
    lambda: _train(T),
    lambda: _train(4, driver="loop"),
    _strict_budget_run,
], ids=["run", "run_eps_at", "train_engine", "train_loop",
        "run_budget_abort"])
def test_batched_readback_equals_reading_each_segment(call, monkeypatch):
    """The report's trajectory, read back in one batched sync, is what
    reading every leaf of every segment back one by one gives: values,
    dtypes, shapes and key order."""
    from repro.api import session as session_mod

    trajs = []
    start = session_mod._copy_to_host_async

    def record(traj):
        trajs.append(traj)
        start(traj)

    monkeypatch.setattr(session_mod, "_copy_to_host_async", record)
    report = call()
    assert len(trajs) == report.counts["dispatches"] > 0
    assert list(report.trajectory) == list(trajs[0])
    assert report.counts["readback_leaves"] == len(trajs) * len(trajs[0])
    for k, got in report.trajectory.items():
        want = np.concatenate([np.asarray(t[k]) for t in trajs])
        assert got.dtype == want.dtype and got.shape == want.shape, k
        assert np.array_equal(got, want), k


def test_compile_count_grows_with_each_new_program():
    before = compile_count()
    jax.jit(lambda x: x * 3.0 + 0.5)(jnp.ones(3)).block_until_ready()
    mid = compile_count()
    jax.jit(lambda x: x * 3.0 + 0.5)  # a wrapper alone compiles nothing
    assert mid > before and compile_count() == mid


def test_span_registers_its_name_and_runs_without_a_profiler():
    with span("repro.test.unit_span", call=3) as s:
        s.set_metadata(extra=1)
    assert "repro.test.unit_span" in HOST_SPANS
    assert HOST_SPANS.keys() >= {SPAN_API_RUN, SPAN_ENGINE_DISPATCH}


def test_profile_join_reads_the_recorded_v5e_trace(tmp_path):
    """A 40-round DPPS consensus job traced on one TPU v5e: the program's
    join finds its dpps_* phases, as the benchmark's reduction does."""
    shutil.copy(RECORDED, tmp_path / "consensus_v5e.xplane.pb")
    hlo = gzip.decompress(RECORDED_HLO.read_bytes()).decode()
    phases, total, note = phase_breakdown(hlo, str(tmp_path))
    assert note is None
    assert {"dpps_perturb", "dpps_sensitivity", "dpps_noise",
            "dpps_gossip"} <= set(phases)
    assert all(phases[p] > 0 for p in phases)
    assert sum(phases.values()) == pytest.approx(total)
    durations = xplane_durations(str(tmp_path))
    assert sum(durations.values()) * 1e-12 == pytest.approx(total)


def test_profile_breakdown_is_read_on_the_cpu():
    session = _session()
    report = session.profile(rounds=3, values=_values())
    assert report.note is None and report.device_total_s > 0
    assert "dpps_gossip" in report.phases
    assert set(report.phases) <= set(KNOWN_PHASES) | {"unattributed"}


def test_an_empty_trace_dir_degrades_to_a_note(tmp_path):
    phases, total, note = phase_breakdown("", str(tmp_path))
    assert phases == {} and total == 0.0 and note
