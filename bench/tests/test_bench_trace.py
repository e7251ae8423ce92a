"""The reduction from a profiler trace to device metrics, on a small
synthetic trace."""
import benchtest  # noqa: F401  (puts bench/ on the path)
import pytest

from benchlib import trace


def _trace(devices):
    planes = [{"name": "/host:CPU", "lines": [{"name": "python", "events": [
        [trace.ANCHOR, 50.0, 1.0]]}]}]
    for i, ops in enumerate(devices):
        planes.append({"name": f"/device:TPU:{i}", "lines": [
            {"name": "XLA Modules", "events": [["jit_seg", 0.0, 1e4]]},
            {"name": "XLA Ops", "events": ops}]})
    planes.append({"name": "/device:CPU:0", "lines": [
        {"name": "XLA Ops", "events": [["host_op", 0.0, 1e4]]}]})
    return {"planes": planes}


def test_busy_is_the_union_of_op_intervals_in_the_window():
    ops = [["%fusion.1 = f32[4] fusion(...)", 100.0, 200.0],
           ["%fusion.2 = f32[4] fusion(...)", 250.0, 100.0],   # overlaps
           ["copy.3", 600.0, 100.0],
           ["copy.3", 950.0, 100.0]]                           # clipped
    out = trace.reduce(_trace([ops]), 0.0, 1000.0)
    assert out["window_s"] == pytest.approx(1e-6)
    # [100, 350] + [600, 700] + [950, 1000] = 400 ns busy
    assert out["busy_s"] == pytest.approx(400e-9)
    assert out["devices"] == 1
    names = [n for n, _ in out["device_ops"]]
    assert names[0] == "fusion.1" and "copy.3" in names
    assert dict(out["device_ops"])["copy.3"] == pytest.approx(150e-9)


def test_idle_gaps_are_apportioned_to_host_spans():
    ops = [["a", 100.0, 100.0], ["b", 500.0, 100.0]]
    spans = [("dispatch", 0.0, 300.0), ("drain", 300.0, 700.0)]
    out = trace.reduce(_trace([ops]), 0.0, 1000.0, spans)
    idle = dict(out["idle_gaps"])
    # gaps [0,100] [200,500] [600,1000]
    assert idle["dispatch"] == pytest.approx(200e-9)
    assert idle["drain"] == pytest.approx(300e-9)
    assert idle["other"] == pytest.approx(300e-9)
    assert sum(idle.values()) == pytest.approx(1e-6 - out["busy_s"])


def test_busy_is_averaged_over_devices_and_host_planes_ignored():
    out = trace.reduce(_trace([[["a", 0.0, 1000.0]], [["a", 0.0, 500.0]]]),
                       0.0, 1000.0)
    assert out["devices"] == 2
    assert out["busy_s"] == pytest.approx(750e-9)


def test_a_trace_without_device_planes_is_refused():
    with pytest.raises(ValueError):
        trace.reduce({"planes": [{"name": "/host:CPU", "lines": []}]},
                     0.0, 1.0)


def test_anchor_and_op_names():
    assert trace.host_event(_trace([[]]), trace.ANCHOR) == (50.0, 51.0)
    assert trace.host_event(_trace([[]]), "absent") is None
    assert trace.op_name("%while.3 = (f32[4]) while(...)") == "while.3"
    assert trace.op_name("copy-start") == "copy-start"
    assert trace.union([(5, 9), (1, 3), (2, 4)], 0, 8) == [[1, 4], [5, 8]]
    assert trace.gaps([[1, 4], [5, 8]], 0, 10) == [(0, 1), (4, 5), (8, 10)]
