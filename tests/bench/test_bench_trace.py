"""The reduction from a profiler trace to device metrics, and the roofline
count, on hand-worked inputs."""
import types

import numpy as np
import pytest

from bench import roofline, trace


def _ev(name, start_ns, duration_ns, stats=()):
    return types.SimpleNamespace(name=name, start_ns=start_ns,
                                 duration_ns=duration_ns, stats=list(stats))


def _plane(name, lines):
    return types.SimpleNamespace(name=name, lines=[
        types.SimpleNamespace(name=n, events=evs) for n, evs in lines])


@pytest.fixture
def fake_trace(monkeypatch):
    """A window 0-1000 ns; device ops: a loop 100-400 holding a kernel
    100-300 and an op 300-400, the kernel again 600-700;
    host spans ``engine.sweep`` 0-450 and ``svc.flush`` 500-1000."""
    planes = [
        _plane("/host:CPU", [("python", [
            _ev(trace.WINDOW_SPAN, 0, 1000), _ev("engine.sweep", 0, 450),
            _ev("svc.flush", 500, 500)])]),
        _plane("/device:TPU:0", [
            ("XLA Modules", [_ev("jit_sweep", 0, 1000)]),
            ("XLA Ops", [
                _ev("%while.2 = (f32[2]) while(...)", 100, 300),
                _ev("%round_fused.6 = (f32[2]) custom-call(...)", 100, 200),
                _ev("%fusion.1 = f32[2] fusion(...)", 300, 100),
                _ev("%round_fused.6 = (f32[2]) custom-call(...)", 600, 100),
                _ev("%round_fused_x.1 = f32[2] fusion(...)", 700, 0),
                _ev("%late.1 = f32[2] fusion(...)", 1500, 100)])]),
        _plane("/device:TPU:0 SparseCore 0", [("XLA Ops", [
            _ev("other", 0, 1000)])]),
    ]
    data = types.SimpleNamespace(planes=planes)
    import jax
    monkeypatch.setattr(jax.profiler.ProfileData, "from_file",
                        staticmethod(lambda path: data))


def test_reduce_trace_by_hand(fake_trace):
    out = trace.reduce_trace("x", kernels={"k": ("round_fused",)},
                             span_names={"engine.sweep", "svc.flush"})
    assert out["devices"] == 1
    assert out["window_s"] == pytest.approx(1000e-9)
    # union of [100, 400) and [600, 700): 400 ns busy
    assert out["busy_s"] == pytest.approx(400e-9)
    assert out["kernel_s"]["k"] == pytest.approx(300e-9)
    ops = dict(out["device_ops"])
    assert ops["round_fused.6"] == pytest.approx(300e-9)
    # the loop's own time: 300 ns less the 200 and 100 ns nested in it
    assert ops["while.2"] == pytest.approx(0.0, abs=1e-15)
    assert "late.1" not in ops
    # gaps [0,100) in engine.sweep; [400,600) 50 ns in engine.sweep, 50
    # in none, 100 in svc.flush; [700,1000) in svc.flush
    gaps = dict(out["idle_gaps"])
    assert gaps["engine.sweep"] == pytest.approx(150e-9)
    assert gaps["svc.flush"] == pytest.approx(400e-9)
    assert gaps[trace.NO_SPAN] == pytest.approx(50e-9)


def test_reduce_trace_needs_a_window(monkeypatch):
    import jax
    data = types.SimpleNamespace(planes=[_plane("/host:CPU", [])])
    monkeypatch.setattr(jax.profiler.ProfileData, "from_file",
                        staticmethod(lambda path: data))
    with pytest.raises(ValueError, match="bench.window"):
        trace.reduce_trace("x")


def test_roofline_count_by_hand():
    """Lane 0 runs 3 rounds from events 0, 10, 40; lane 1 one round from
    0. Rounds read 100 - 0, 100 - 10 and 100 - 40 rows of a 100-event
    log."""
    num_rounds = np.array([3, 1])
    boundaries = np.array([[0, 10, 40, 100, 0],
                           [0, 100, 0, 0, 0]])
    assert roofline.sweep_log_rows(num_rounds, boundaries, 100) == 250
    assert roofline.sweep_log_bytes(num_rounds, boundaries, 100, 7) \
        == 250 * 7 * 4


CHIP_TRACE = trace.os.path.join(
    trace.os.path.dirname(trace.os.path.dirname(trace.os.path.abspath(
        trace.__file__))), "bench", "testdata", "chip_sweep.xplane.pb")


def test_reduce_a_trace_recorded_on_the_chip():
    """Two fused-round sweeps (N=65,536, C=100, S=4) on one v5e, with the
    benchmark's host spans; recomputed here from the raw events."""
    import jax
    out = trace.reduce_trace(CHIP_TRACE, kernels={"rf": ("round_fused",)},
                             span_names={"engine.sweep",
                                         "block_until_ready"})
    data = jax.profiler.ProfileData.from_file(CHIP_TRACE)
    host = {p.name: p for p in data.planes}["/host:CPU"]
    windows = [(e.start_ns, e.start_ns + e.duration_ns)
               for line in host.lines for e in line.events
               if e.name == trace.WINDOW_SPAN]
    (w0, w1), = windows
    device = {p.name: p for p in data.planes}["/device:TPU:0"]
    ops, = [line for line in device.lines if line.name == "XLA Ops"]
    covered, end = 0, w0
    kernel = 0
    for e in sorted(ops.events, key=lambda e: e.start_ns):
        a = max(e.start_ns, w0)
        b = min(e.start_ns + e.duration_ns, w1)
        if b <= a:
            continue
        if e.name.startswith("%round_fused."):
            kernel += b - a
        covered += max(0, b - max(a, end))
        end = max(end, b)
    assert out["devices"] == 1
    assert out["window_s"] == pytest.approx((w1 - w0) * 1e-9)
    assert out["busy_s"] == pytest.approx(covered * 1e-9)
    assert out["kernel_s"]["rf"] == pytest.approx(kernel * 1e-9)
    assert 0 < out["kernel_s"]["rf"] < out["busy_s"] < out["window_s"]
    gaps = dict(out["idle_gaps"])
    assert sum(gaps.values()) == pytest.approx(
        out["window_s"] - out["busy_s"])
    assert set(gaps) <= {"engine.sweep", "block_until_ready", trace.NO_SPAN}
    # the kernel leads the device's own time; the loop op holds its body
    assert out["device_ops"][0][0].startswith("round_fused.")
