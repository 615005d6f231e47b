"""The trace reduction: busy union, per-phase join, idle gaps named by the
host span around them; on a hand-built trace and on one recorded on a
TPU v5e (committed under bench/testdata)."""
import gzip
import pathlib
import sys
from types import SimpleNamespace as NS

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "bench"), str(ROOT / "src")]

from benchlib import trace  # noqa: E402

RECORDED = ROOT / "bench" / "testdata" / "consensus_v5e.xplane.pb"
RECORDED_HLO = ROOT / "bench" / "testdata" / "consensus_v5e.hlo.txt.gz"


def ev(name, start, dur, **stats):
    return NS(name=name, start_ns=float(start), duration_ns=float(dur),
              stats=list(stats.items()))


def profile():
    host = NS(name="/host:CPU", lines=[NS(name="python", events=[
        ev("bench.window", 0, 1000),
        ev("bench.job", 0, 600),
        ev("bench.readback", 500, 100),
        ev("other", 0, 1000),
    ])])
    ops = [   # named as the TPU trace names them: by HLO instruction text
        ev("%while.1 = (f32[8]) while((f32[8]) %p), body=%b", 100, 150),
        ev("%fusion.1 = f32[8]{0} fusion(f32[8]{0} %x)", 100, 100),
        ev("%fusion.2 = f32[8]{0} fusion(f32[8]{0} %x)", 200, 50),
        ev("%copy.3 = f32[8]{0} copy(f32[8]{0} %y)", 300, 50),
        ev("%fusion.4 = f32[8]{0} fusion(f32[8]{0} %z)", 700, 100),
        ev("%late = f32[8]{0} copy(f32[8]{0} %z)", 950, 200),  # clipped
        ev("%fusion.1 = f32[8]{0} fusion(f32[8]{0} %w)", 860, 40),
    ]
    modules = [ev("jit_run(123)", 90, 300), ev("jit_run(123)", 690, 120),
               ev("jit_other(9)", 850, 60), ev("jit_run(123)", 940, 300)]
    dev = NS(name="/device:TPU:0", lines=[NS(name="XLA Ops", events=ops),
                                          NS(name="XLA Modules",
                                             events=modules)])
    return NS(planes=[host, dev, NS(name="/device:TPU:1", lines=[])])


HLO = """HloModule jit_run, entry_computation_layout={()->f32[8]}
  %fusion.1 = f32[8]{0} fusion(%p), kind=kLoop, metadata={op_name="jit(run)/while/body/dpps_noise/mul"}
  %fusion.2 = f32[8]{0} fusion(%p), kind=kLoop, metadata={op_name="jit(run)/while/body/dpps_gossip/pushsum_mix/dot"}
  ROOT %fusion.4 = f32[8]{0} fusion(%q), metadata={op_name="jit(run)/engine_unpack/slice"}
"""


def test_busy_phases_and_gaps():
    s = trace.reduce(profile(), n_devices=1, hlo_texts=(HLO,))
    assert s.window_s == pytest.approx(1000e-9)
    # union: [100, 250) + [300, 350) + [700, 800) + [860, 900)
    #        + [950, 1000) = 390 ns; the while holds its body, so it is
    #        busy time but not an op of its own
    assert s.busy_s == pytest.approx(390e-9)
    assert sum(s.device_s) == pytest.approx(390e-9)
    assert s.n_ops == 6
    # fusion.1 of jit_other shares the name but not the module
    assert s.phase_s["dpps_noise"] == pytest.approx(100e-9)
    assert s.phase_s["dpps_gossip"] == pytest.approx(50e-9)  # outermost
    assert s.phase_s["engine_unpack"] == pytest.approx(100e-9)
    # [350, 700): its midpoint lies in bench.readback, inside bench.job
    assert s.idle_gaps[0] == ["readback", pytest.approx(350e-9)]
    assert sorted(v for n, v in s.idle_gaps if n == "job") == [
        pytest.approx(50e-9), pytest.approx(100e-9)]     # [250, 300), [0, 100)
    assert s.device_ops[0] == ["dpps_noise/fusion.1", pytest.approx(100e-9)]
    assert sorted(v for n, v in s.idle_gaps if n == "untraced_host") == [
        pytest.approx(50e-9), pytest.approx(60e-9)]      # [900, 950), [800, 860)
    assert len(s.device_ops) <= 10 and len(s.idle_gaps) <= 10
    assert s.breakdown() == {"device_ops": s.device_ops,
                             "idle_gaps": s.idle_gaps}


def test_a_trace_without_a_window_is_refused():
    p = profile()
    p.planes[0].lines[0].events.pop(0)
    with pytest.raises(ValueError):
        trace.reduce(p, n_devices=1)


def test_hlo_phase_map():
    assert trace.hlo_phase_map(HLO) == {"fusion.1": "dpps_noise",
                                        "fusion.2": "dpps_gossip",
                                        "fusion.4": "engine_unpack"}
    assert trace.instruction("%fusion.2 = f32[8]{0} fusion(%p)") == "fusion.2"


def test_recorded_v5e_trace():
    """A 40-round DPPS consensus job (16 nodes, 7,850 shared values, two
    20-round segments) traced on one TPU v5e, with its compiled HLO."""
    from jax.profiler import ProfileData

    hlo = gzip.decompress(RECORDED_HLO.read_bytes()).decode()
    s = trace.reduce(ProfileData.from_file(str(RECORDED)), n_devices=1,
                     hlo_texts=(hlo,))
    assert 0 < s.busy_s <= s.window_s
    assert s.n_ops > 100
    assert {"dpps_noise", "dpps_gossip", "dpps_perturb",
            "dpps_sensitivity"} <= set(s.phase_s)
    assert sum(s.phase_s.values()) <= sum(s.device_s) * (1 + 1e-9)
    assert sum(s.device_s) <= s.busy_s * (1 + 1e-9)
    assert s.idle_gaps and all(n == "job" for n, _ in s.idle_gaps[:3])
