"""The observability layer (repro.obs): phase scopes are metadata-only
(HLO bit-identical with and without), the watchdog's in-scan wire stats
ride the trajectory without perturbing the run, every producer hook
composes bit-transparently, the bus/exporters round-trip events, the
wall-clock split sums to the old lump, and Session.profile produces a
per-phase device-time breakdown when the xplane bindings exist."""
import json
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import (
    BudgetExhausted,
    BudgetHook,
    LedgerHook,
    MetricsHook,
    PrivacySpec,
    RoundHook,
    RunAbort,
    Session,
    TranscriptHook,
    hook_trace_spec,
)
from repro.core.topology import DOutGraph, calibrate_constants
from repro.engine import ProtocolPlan
from repro.net import NetworkStatsHook
from repro.obs import (
    JsonlExporter,
    KNOWN_PHASES,
    MetricsBus,
    ProfileReport,
    WatchdogAbort,
    WatchdogHook,
    phase,
    prometheus_text,
)
from repro.obs.trace import (
    PHASE_DPPS_GOSSIP,
    PHASE_DPPS_NOISE,
    PHASE_DPPS_PERTURB,
    PHASE_DPPS_SENSITIVITY,
    PHASE_DPPS_SYNC,
    hlo_phase_map,
)

N, T = 8, 6
TOPO = DOutGraph(n_nodes=N, d=2)
CP, LAM = calibrate_constants(TOPO)


def _s0(seed=0):
    key = jax.random.PRNGKey(seed)
    return [jax.random.normal(key, (N, 11)),
            jax.random.normal(jax.random.fold_in(key, 1), (N, 2, 3))]


def _session(**kw):
    kw.setdefault("privacy", PrivacySpec(b=5.0, gamma_n=0.02,
                                         c_prime=CP, lam=LAM))
    kw.setdefault("sync_interval", 3)
    return Session.build(TOPO, **kw)


def _strip_hlo_noise(txt: str) -> str:
    """Compiled HLO without its debug information: the per-op metadata
    and the module's source-location tables (FileNames ... StackFrames)."""
    txt = re.sub(r"metadata=\{[^}]*\}", "", txt)
    txt = re.sub(r"^(?:FileNames|FunctionNames|FileLocations|StackFrames)"
                 r"\n(?:.+\n)*", "", txt, flags=re.M)
    return re.sub(r'"[^"]*source_file[^"]*"', "", txt)


def _assert_trees_equal(a, b):
    for x, y in zip(jax.tree_util.tree_leaves(a),
                    jax.tree_util.tree_leaves(b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# ---------------------------------------------------------------------------
# Phase scopes: metadata-only annotation, visible in compiled op_name
# ---------------------------------------------------------------------------

def test_phase_scope_is_metadata_only():
    """The same computation with and without a phase() scope compiles to
    identical HLO once metadata is stripped — the mechanism behind the
    golden pins staying binding with scopes all over the hot path."""
    def _mk(scoped):
        def f(x):
            if scoped:
                with phase("unit_test_scope"):
                    return x * 2.0 + 1.0
            return x * 2.0 + 1.0
        return f

    bare = jax.jit(_mk(False)).lower(1.0).compile().as_text()
    scoped = jax.jit(_mk(True)).lower(1.0).compile().as_text()
    assert _strip_hlo_noise(bare) == _strip_hlo_noise(scoped)
    assert "unit_test_scope" in KNOWN_PHASES


def _xlstm_grad_hlo(monkeypatch=None):
    """Compiled HLO of a tiny xLSTM Transformer's loss gradient; with
    ``monkeypatch`` the model's phase scopes are replaced by no-ops."""
    import contextlib

    from repro.models import Transformer
    from repro.models import transformer as tf
    from repro.models.config import ModelConfig, XLSTMGroup

    if monkeypatch is not None:
        monkeypatch.setattr(tf, "phase",
                            lambda name: contextlib.nullcontext())
    model = Transformer(ModelConfig(
        name="tiny-xlstm", d_model=16, vocab_size=32, n_heads=2,
        n_kv_heads=2, head_dim=16, d_ff=0, tie_embedding=True,
        groups=(XLSTMGroup(n_units=1, mlstm_per_unit=2, proj_factor=2.0),)))
    params = model.init(jax.random.PRNGKey(0))
    batch = {"tokens": jnp.zeros((2, 6), jnp.int32)}
    return jax.jit(jax.grad(model.loss_fn)).lower(
        params, batch).compile().as_text()


def test_model_block_scopes_name_the_gradient_and_change_no_op(monkeypatch):
    """The model's block scopes reach the compiled gradient's op_name —
    forward and backward — and are metadata only: the program is the
    unscoped one once debug information is stripped."""
    def components(hlo):
        return {part for name in re.findall(r'op_name="([^"]*)"', hlo)
                for part in name.split("/")}

    scoped = _xlstm_grad_hlo()
    named = components(scoped)
    for name in ("model_embed", "model_mlstm", "model_slstm", "model_head"):
        assert name in KNOWN_PHASES
        assert any(name in part for part in named), name
    assert any(p.startswith("transpose(jvp(") for p in named)
    bare = _xlstm_grad_hlo(monkeypatch)
    assert not any("model_" in part for part in components(bare))
    assert _strip_hlo_noise(bare) == _strip_hlo_noise(scoped)


def test_round_phases_annotate_compiled_hlo():
    """Every DPPS phase name survives into the compiled segment's op_name
    metadata — the join key Session.profile attributes device time by."""
    session = _session()
    s0 = _s0()
    state = session.consensus_state(s0)
    eps = [jnp.zeros((T,) + x.shape, x.dtype) for x in s0]
    hlo = session.consensus_runner(()).lower(
        state, eps, jax.random.PRNGKey(0)).compile().as_text()
    for name in (PHASE_DPPS_PERTURB, PHASE_DPPS_SENSITIVITY,
                 PHASE_DPPS_NOISE, PHASE_DPPS_GOSSIP, PHASE_DPPS_SYNC):
        assert name in hlo, f"phase {name} missing from compiled metadata"
    instr_phase = hlo_phase_map(hlo)
    assert set(instr_phase.values()) >= {
        PHASE_DPPS_PERTURB, PHASE_DPPS_NOISE, PHASE_DPPS_GOSSIP}


# ---------------------------------------------------------------------------
# Watchdog: in-scan wire stats + host-side judgement
# ---------------------------------------------------------------------------

def test_watchdog_wire_stats_ride_trajectory_bit_transparently():
    session = _session()
    s0, key = _s0(), jax.random.PRNGKey(7)
    plain = session.run(T, values=s0, key=key)
    hook = WatchdogHook(warn=lambda s: None, bus=MetricsBus())
    watched = session.run(T, values=s0, hooks=[hook], key=key)

    for row in ("wd_nonfinite", "wd_mass_drift", "wd_consensus_residual"):
        assert watched.trajectory[row].shape == (T,)
    _assert_trees_equal(plain.state.push, watched.state.push)
    np.testing.assert_array_equal(plain.trajectory["sensitivity_estimate"],
                                  watched.trajectory["sensitivity_estimate"])
    assert hook.alerts == []  # a healthy run raises nothing


def test_watchdog_flags_nonfinite_wire_and_strict_aborts():
    session = _session(chunk=3)
    s0 = _s0()
    s0[0] = s0[0].at[2, 4].set(jnp.nan)

    lines = []
    hook = WatchdogHook(warn=lines.append, bus=MetricsBus())
    report = session.run(T, values=s0, hooks=[hook])
    assert not report.aborted
    checks = {a.check for a in hook.alerts}
    assert "nonfinite_wire" in checks
    first = next(a for a in hook.alerts if a.check == "nonfinite_wire")
    assert first.severity == "critical" and first.round == 0
    assert any("non-finite" in line for line in lines)
    alerts = hook.bus.events("alert")
    assert any(e.name == "watchdog.nonfinite_wire" for e in alerts)

    strict = WatchdogHook(strict=True, warn=lambda s: None, bus=MetricsBus())
    report = session.run(T, values=s0, hooks=[strict])
    assert report.aborted and "watchdog" in report.abort_reason
    assert report.rounds == 3  # first segment consumed, rest skipped


def test_watchdog_abort_is_a_run_abort():
    assert issubclass(WatchdogAbort, RunAbort)
    assert issubclass(BudgetExhausted, RunAbort)


def test_watchdog_sensitivity_gap_direct():
    hook = WatchdogHook(strict=True, warn=lambda s: None, bus=MetricsBus())
    rows = {
        "wd_nonfinite": np.zeros(4, np.int32),
        "wd_mass_drift": np.zeros(4),
        "wd_consensus_residual": np.full(4, 0.5),
        "sensitivity_estimate": np.full(4, 1.0),
        "sensitivity_real": np.array([0.5, 0.9, 1.5, 0.2]),
    }
    with pytest.raises(WatchdogAbort) as exc:
        hook.consume(rows, t0=10)
    assert exc.value.alert.check == "sensitivity_gap"
    assert exc.value.alert.round == 12  # first violating round, absolute


def test_watchdog_mass_drift_and_residual_trend_warn_only():
    hook = WatchdogHook(strict=True, trend_window=4, mass_tol=1e-3,
                        warn=lambda s: None, bus=MetricsBus())
    rows = {
        "wd_nonfinite": np.zeros(4, np.int32),
        "wd_mass_drift": np.array([0.0, 0.05, 0.0, 0.0]),
        "wd_consensus_residual": np.array([1.0, 1.0, 100.0, 100.0]),
    }
    hook.consume(rows, t0=0)  # strict, but warn-severity: no raise
    checks = [a.check for a in hook.alerts]
    assert "mass_drift" in checks and "residual_trend" in checks
    drift = next(a for a in hook.alerts if a.check == "mass_drift")
    assert drift.round == 1 and drift.severity == "warn"


# ---------------------------------------------------------------------------
# Composition: the full producer pipeline is bit-transparent
# ---------------------------------------------------------------------------

def _producer_pipeline():
    return {
        "transcript": TranscriptHook(),
        "ledger": LedgerHook(bus=MetricsBus()),
        "budget": BudgetHook(1e9, warn=lambda s: None),
        "metrics": MetricsHook(fields={"sens": "sensitivity_estimate"},
                               log_every=100, print_fn=lambda s: None,
                               bus=MetricsBus()),
        "netstats": NetworkStatsHook(bus=MetricsBus()),
        "watchdog": WatchdogHook(warn=lambda s: None, bus=MetricsBus()),
    }


@pytest.mark.parametrize("schedule", ["dense", "sparse"])
@pytest.mark.parametrize("packed", [True, False], ids=["packed", "pytree"])
def test_full_hook_pipeline_bit_matches_hookless_and_solo(schedule, packed):
    """All six producers at once leave the run bit-identical to hookless
    AND each hook's collected output bit-identical to its solo run."""
    plan = ProtocolPlan.from_topology(TOPO, schedule=schedule,
                                      use_kernels=False, sync_interval=3,
                                      packed=packed)
    session = _session(plan=plan)
    s0, key = _s0(), jax.random.PRNGKey(21)
    plain = session.run(T, values=s0, key=key)

    solo = _producer_pipeline()
    for hook in solo.values():
        session.run(T, values=s0, hooks=[hook], key=key)
    combo = _producer_pipeline()
    full = session.run(T, values=s0, hooks=list(combo.values()), key=key)

    _assert_trees_equal(plain.state.push, full.state.push)
    for row in plain.trajectory:
        np.testing.assert_array_equal(plain.trajectory[row],
                                      full.trajectory[row])

    np.testing.assert_array_equal(solo["transcript"].transcript().messages,
                                  combo["transcript"].transcript().messages)
    assert combo["ledger"].ledger.entries == solo["ledger"].ledger.entries
    assert combo["metrics"].history == solo["metrics"].history
    assert len(combo["metrics"].history) == T
    assert combo["watchdog"].alerts == solo["watchdog"].alerts == []
    np.testing.assert_array_equal(
        solo["netstats"].network_stats().realized_edges,
        combo["netstats"].network_stats().realized_edges)
    assert full.network is not None and full.network.rounds == T
    assert full.epsilon_spent == pytest.approx(plain.epsilon_spent)


# ---------------------------------------------------------------------------
# Wall-clock split
# ---------------------------------------------------------------------------

def test_run_report_wall_clock_split():
    session = _session(chunk=2)
    report = session.run(T, values=_s0())
    assert report.compile_s > 0.0 and report.run_s >= 0.0
    assert report.wall_clock == report.compile_s + report.run_s
    summary = report.summary()
    assert summary["compile_s"] == pytest.approx(report.compile_s, abs=1e-3)
    assert summary["run_s"] == pytest.approx(report.run_s, abs=1e-3)
    assert summary["wall_clock_s"] == pytest.approx(report.wall_clock,
                                                    abs=1e-3)


# ---------------------------------------------------------------------------
# NetworkStatsHook is a real RoundHook
# ---------------------------------------------------------------------------

def test_network_stats_hook_is_round_hook_with_trace_spec():
    hook = NetworkStatsHook(bus=MetricsBus())
    assert isinstance(hook, RoundHook)
    spec = hook_trace_spec((hook,))
    assert spec.needs_adjacency and spec.tap is None
    assert not spec.needs_s_half and not spec.needs_wire_stats

    session = _session()
    session.run(T, values=_s0(), hooks=[hook])
    stats = hook.network_stats()
    counters = hook.bus.snapshot()["counters"]
    assert counters["net.realized_edges"] == float(
        stats.realized_edges.sum())
    assert counters["net.dropped_edges"] == 0.0


# ---------------------------------------------------------------------------
# Bus + exporters
# ---------------------------------------------------------------------------

def test_bus_aggregates_and_ring():
    bus = MetricsBus(ring=3)
    bus.count("c", 2.0)
    bus.count("c", 3.0)
    bus.gauge("g", 7.0, labels=[("node", "1")])
    bus.gauge("g", 9.0, labels=[("node", "1")])
    for v in (1.0, 5.0, 3.0):
        bus.observe("h", v)
    snap = bus.snapshot()
    assert snap["counters"]["c"] == 5.0
    assert snap["gauges"]["g{node=1}"] == 9.0
    assert snap["histograms"]["h"] == {"count": 3, "sum": 9.0,
                                       "min": 1.0, "max": 5.0}
    assert len(bus.events()) == 3  # ring bounded

    seen = []
    detach = bus.subscribe(seen.append)
    bus.count("c")
    detach()
    bus.count("c")
    assert len(seen) == 1 and seen[0].name == "c"

    with pytest.raises(ValueError):
        from repro.obs import Event
        bus.emit(Event(ts=0.0, kind="bogus", name="x", value=1.0))


def test_jsonl_exporter_round_trips(tmp_path):
    path = tmp_path / "events.jsonl"
    bus = MetricsBus()
    with JsonlExporter(str(path)).attach(bus) as exporter:
        bus.count("privacy.rounds", 3.0, round=2)
        bus.alert("watchdog.mass_drift", "drifting", value=0.1, round=5,
                  labels=[("severity", "warn")])
        assert exporter.written == 2
    lines = [json.loads(ln) for ln in path.read_text().splitlines()]
    assert lines[0]["kind"] == "counter" and lines[0]["value"] == 3.0
    assert lines[1] == {"ts": lines[1]["ts"], "kind": "alert",
                        "name": "watchdog.mass_drift", "value": 0.1,
                        "labels": {"severity": "warn"}, "round": 5,
                        "message": "drifting"}
    bus.count("after.detach")  # exporter closed: must not raise or write
    assert len(path.read_text().splitlines()) == 2


def test_prometheus_text_exposition():
    bus = MetricsBus()
    bus.count("privacy.rounds", 4.0)
    bus.gauge("privacy.epsilon_total", 1.25)
    bus.observe("round.loss", 0.5)
    bus.observe("round.loss", 1.5)
    text = prometheus_text(bus)
    assert "# TYPE privacy_rounds counter" in text
    assert "privacy_rounds 4.0" in text
    assert "privacy_epsilon_total 1.25" in text
    assert "round_loss_count 2" in text
    assert "round_loss_sum 2.0" in text


def test_hook_sinks_default_to_obs_logger(capsys):
    hook = BudgetHook(1.0)
    hook.warn("over budget soon")
    assert "over budget soon" in capsys.readouterr().out


def test_hooks_publish_to_bus():
    session = _session()
    ledger = LedgerHook(bus=MetricsBus())
    metrics = MetricsHook(log_every=100, print_fn=lambda s: None,
                          bus=MetricsBus())
    session.run(T, values=_s0(), hooks=[ledger, metrics])
    snap = ledger.bus.snapshot()
    assert snap["counters"]["privacy.rounds"] == float(T)
    assert snap["gauges"]["privacy.epsilon_total"] > 0.0
    assert any(k.startswith("metrics.") for k in
               metrics.bus.snapshot()["gauges"])


# ---------------------------------------------------------------------------
# Session.profile
# ---------------------------------------------------------------------------

def test_session_profile_breakdown():
    session = _session()
    report = session.profile(rounds=4, values=_s0())
    assert isinstance(report, ProfileReport)
    assert report.rounds == 4 and report.backend == jax.default_backend()
    assert report.trace_s > 0 and report.compile_s > 0
    assert report.execute_s > 0
    assert report.wall_clock == pytest.approx(
        report.trace_s + report.compile_s + report.execute_s)
    if report.phases:  # xplane protobuf importable: real breakdown
        assert report.device_total_s > 0
        known = set(KNOWN_PHASES) | {"unattributed"}
        assert set(report.phases) <= known
        assert PHASE_DPPS_GOSSIP in report.phases
        assert sum(report.phases.values()) == pytest.approx(
            report.device_total_s)
    else:  # jax-only environment: wall split still works, note explains
        assert report.note is not None
    summary = report.summary()
    assert {"rounds", "trace_s", "compile_s", "execute_s",
            "wall_clock_s", "phases"} <= set(summary)


def test_hlo_phase_map_parses_op_name_metadata():
    hlo = '\n'.join([
        '  %multiply.1 = f32[8]{0} multiply(a, b), metadata={'
        'op_name="jit(run)/while/body/dpps_gossip/mul" '
        'source_file="x.py"}',
        '  %add.2 = f32[8]{0} add(c, d), metadata={'
        'op_name="jit(run)/while/body/other/add"}',
        '  ROOT %tuple.3 = tuple(e)',
    ])
    assert hlo_phase_map(hlo) == {"multiply.1": PHASE_DPPS_GOSSIP}


# ---------------------------------------------------------------------------
# Run timeline: Chrome-trace export of segment spans + async lifecycle
# ---------------------------------------------------------------------------

def _timeline_session():
    from repro.net import DelayModel
    return _session(sync_interval=0, chunk=4,
                    delays=DelayModel(max_delay=2, timeout_rate=0.3, seed=1))


def test_timeline_hook_records_chrome_trace(tmp_path):
    from repro.obs import TimelineHook, validate_chrome_trace

    path = tmp_path / "trace.json"
    bus = MetricsBus()
    hook = TimelineHook(str(path), bus=bus)
    report = _timeline_session().run(12, values=_s0(), hooks=[hook])
    obj = json.loads(path.read_text())
    validate_chrome_trace(obj)
    evs = obj["traceEvents"]

    # Host track: one span per compiled segment (12 rounds / chunk 4),
    # the first labelled as the trace/compile+execute lump, plus one
    # hook-consume span each; durations sum within the wall clock.
    segs = [e for e in evs if e.get("cat") == "segment" and e["tid"] == 1]
    assert len(segs) == 3
    assert segs[0]["name"] == "trace/compile+execute"
    assert all(e["name"] == "execute" for e in segs[1:])
    consumes = [e for e in evs if e["name"] == "hook-consume"]
    assert len(consumes) == 3
    total_us = sum(e["dur"] for e in segs + consumes)
    assert total_us <= (report.compile_s + report.run_s) * 1e6 * 1.05

    # Protocol track: the async lifecycle must include both outcomes —
    # send->deliver spans (balanced b/e pairs, counted multiplicity) and
    # send->timeout instants (timeout_rate=0.3 guarantees some in 12
    # rounds).
    sends = [e for e in evs if e["ph"] == "b"]
    assert sends and all(e["name"].startswith("msg send->deliver")
                         for e in sends)
    assert all(e["args"]["deliver_round"]
               == e["args"]["enqueue_round"] + e["args"]["delay_rounds"]
               for e in sends)
    touts = [e for e in evs if e["ph"] == "i"
             and e["name"] == "msg send->timeout"]
    assert touts and all(e["args"]["count"] >= 1 for e in touts)
    counters = [e for e in evs if e["ph"] == "C" and e["name"] == "async"]
    assert len(counters) == 12  # one sample per round
    assert {"inflight_mass", "active_nodes", "staleness_max"} <= set(
        counters[0]["args"])

    # Run metadata + the bus side: wall-split gauges and per-segment
    # histograms.
    meta = obj["otherData"]
    assert meta["rounds"] == 12 and meta["max_delay"] == 2
    snap = bus.snapshot()
    assert snap["gauges"]["run.compile_s"] == pytest.approx(report.compile_s)
    assert snap["gauges"]["run.run_s"] == pytest.approx(report.run_s)
    assert snap["histograms"]["timeline.execute_s"]["count"] == 3


def test_timeline_hook_is_bit_transparent():
    from repro.obs import TimelineHook

    session = _timeline_session()
    bare = session.run(8, values=_s0())
    timed = session.run(8, values=_s0(), hooks=[TimelineHook(
        bus=MetricsBus())])
    _assert_trees_equal(bare.state, timed.state)
    _assert_trees_equal(bare.trajectory, timed.trajectory)


def test_timeline_add_profile_lays_out_device_slices():
    from repro.obs import Timeline, validate_chrome_trace
    from repro.obs.timeline import PID_DEVICE

    profile = ProfileReport(
        rounds=10, backend="cpu", trace_s=0.1, compile_s=0.4,
        execute_s=0.5, device_total_s=0.3,
        phases={"dpps_gossip": 0.2, "dpps_noise": 0.1})
    tl = Timeline()
    tl.span("execute", 5.0, 1.0, cat="segment")
    tl.add_profile(profile)
    obj = tl.to_chrome_trace()
    validate_chrome_trace(obj)
    host = {e["name"]: e for e in obj["traceEvents"]
            if e.get("cat") == "profile"}
    assert {"profile:trace", "profile:compile",
            "profile:execute"} <= set(host)
    # Sequential layout after the last recorded event.
    assert host["profile:compile"]["ts"] == pytest.approx(
        host["profile:trace"]["ts"] + host["profile:trace"]["dur"])
    dev = [e for e in obj["traceEvents"] if e.get("pid") == PID_DEVICE
           and e["ph"] == "X"]
    assert [e["name"] for e in dev] == ["dpps_gossip", "dpps_noise"]
    # Device slices sit under the execute window.
    assert dev[0]["ts"] >= host["profile:execute"]["ts"] - 1e-6
    assert obj["otherData"]["profile"]["device_total_s"] == 0.3


def test_validate_chrome_trace_rejects_malformed():
    from repro.obs import validate_chrome_trace

    ok = {"traceEvents": [{"name": "x", "ph": "X", "pid": 1, "tid": 1,
                           "ts": 0, "dur": 5}]}
    validate_chrome_trace(ok)
    with pytest.raises(ValueError, match="traceEvents"):
        validate_chrome_trace({"foo": []})
    with pytest.raises(ValueError, match="unknown phase"):
        validate_chrome_trace({"traceEvents": [
            {"name": "x", "ph": "Z", "pid": 1, "tid": 1, "ts": 0}]})
    with pytest.raises(ValueError, match="dur"):
        validate_chrome_trace({"traceEvents": [
            {"name": "x", "ph": "X", "pid": 1, "tid": 1, "ts": 0}]})
    with pytest.raises(ValueError, match="missing id"):
        validate_chrome_trace({"traceEvents": [
            {"name": "x", "ph": "b", "pid": 1, "tid": 1, "ts": 0,
             "cat": "m"}]})
    with pytest.raises(ValueError, match="unbalanced"):
        validate_chrome_trace({"traceEvents": [
            {"name": "x", "ph": "b", "pid": 1, "tid": 1, "ts": 0,
             "cat": "m", "id": 3}]})


def test_metrics_hook_publishes_run_wall_split():
    bus = MetricsBus()
    session = _session()
    report = session.run(T, values=_s0(),
                         hooks=[MetricsHook(log_every=10**9,
                                            print_fn=lambda s: None,
                                            bus=bus)])
    snap = bus.snapshot()
    assert snap["gauges"]["run.compile_s"] == pytest.approx(report.compile_s)
    assert snap["gauges"]["run.run_s"] == pytest.approx(report.run_s)


# ---------------------------------------------------------------------------
# Bus ring drop accounting + exposition edge cases
# ---------------------------------------------------------------------------

def test_bus_ring_drop_counter(tmp_path):
    bus = MetricsBus(ring=2)
    assert bus.dropped == 0
    path = tmp_path / "events.jsonl"
    exporter = JsonlExporter(str(path)).attach(bus)
    for i in range(5):
        bus.count("c")
    assert bus.dropped == 3
    # Aggregates and subscribers never lost anything — only the ring.
    assert bus.snapshot()["counters"]["c"] == 5.0
    assert bus.snapshot()["counters"]["bus.dropped"] == 3.0
    assert bus.series()["counters"][("bus.dropped", ())] == 3.0
    assert len(bus.events()) == 2
    assert "bus_dropped 3.0" in prometheus_text(bus)
    exporter.close()
    lines = [json.loads(ln) for ln in path.read_text().splitlines()]
    assert len(lines) == 6  # 5 streamed + the closing bus.dropped line
    assert lines[-1]["name"] == "bus.dropped" and lines[-1]["value"] == 3.0

    fresh = MetricsBus(ring=2)
    fresh.count("c")
    assert fresh.dropped == 0
    assert "bus.dropped" not in fresh.snapshot()["counters"]
    assert "bus_dropped" not in prometheus_text(fresh)


def test_prometheus_label_escaping_and_nonfinite():
    bus = MetricsBus()
    bus.gauge("g", 1.0, labels=[("path", 'a"b\\c\nd')])
    bus.gauge("nanval", float("nan"))
    bus.gauge("posinf", float("inf"))
    bus.gauge("neginf", float("-inf"))
    text = prometheus_text(bus)
    assert r'g{path="a\"b\\c\nd"} 1.0' in text
    assert "nanval NaN" in text
    assert "posinf +Inf" in text
    assert "neginf -Inf" in text


def test_prometheus_empty_histogram_renders_nan_bounds():
    from repro.obs.metrics import HistogramSummary

    bus = MetricsBus()
    bus._hists[("h", ())] = HistogramSummary()  # created, never observed
    text = prometheus_text(bus)
    assert "h_count 0" in text
    assert "h_min NaN" in text and "h_max NaN" in text
    assert "+Inf" not in text and "-Inf" not in text
