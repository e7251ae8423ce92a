"""The engine's spans read for the benchmark: innermost-span attribution
of idle device time on a synthetic trace, and the readers on a small
engine run."""
import time

import benchtest  # noqa: F401  (puts bench/ and src/ on the path)
import numpy as np
import pytest

from benchlib import engine_spans as es


def _trace(host_events, ops):
    return {"planes": [
        {"name": "/host:CPU", "lines": [
            {"name": "python", "events": host_events},
            {"name": "other thread", "events": [["unrelated", 0.0, 1e4]]}]},
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": [
                ["jit_placement_segment(1)", 100.0, 100.0],
                ["jit_placement_segment(1)", 500.0, 100.0],
                ["jit_other(2)", 900.0, 50.0]]},
            {"name": "XLA Ops", "events": ops}]}]}


# segment [0, 400] holds segment.call [50, 250] and hook [300, 350];
# drain [400, 1000] holds drain.records [450, 700]; [700, ...] and the
# tail after 1000 are drain's own; nothing is open in [1000, 1200]
HOST = [["segment", 0.0, 400.0], ["segment.call", 50.0, 200.0],
        ["hook", 300.0, 50.0], ["drain", 400.0, 600.0],
        ["drain.records", 450.0, 250.0], ["bench_clock_anchor", 5.0, 1.0]]
OPS = [["a", 100.0, 100.0], ["b", 500.0, 100.0]]


def test_host_events_finds_annotations_by_name():
    ev = es.host_events(_trace(HOST, OPS), ["drain", "hook"])
    assert ev == [("hook", 300.0, 350.0), ("drain", 400.0, 1000.0)]
    assert es.host_events(_trace(HOST, OPS), ["absent"]) == []


def test_innermost_partitions_the_window():
    spans = es.host_events(_trace(HOST, OPS), es.NAMES)
    parts = es.innermost(spans, 0.0, 1200.0)
    assert parts == [("segment", 0.0, 50.0), ("segment.call", 50.0, 250.0),
                     ("segment", 250.0, 300.0), ("hook", 300.0, 350.0),
                     ("segment", 350.0, 400.0), ("drain", 400.0, 450.0),
                     ("drain.records", 450.0, 700.0),
                     ("drain", 700.0, 1000.0), ("other", 1000.0, 1200.0)]
    clipped = es.innermost(spans, 320.0, 500.0)
    assert clipped[0] == ("hook", 320.0, 350.0)
    assert clipped[-1] == ("drain.records", 450.0, 500.0)


def test_idle_goes_to_the_innermost_span_once():
    tr = _trace(HOST, OPS)
    idle = dict(es.idle_by_span(tr, 0.0, 1200.0))
    # busy [100, 200] and [500, 600]: 1000 ns idle of 1200
    assert sum(idle.values()) == pytest.approx(1000e-9)
    # segment.call [50,100] [200,250]; segment [0,50] [250,300] [350,400];
    # drain [400,450] [700,1000]; drain.records [450,500] [600,700]
    assert idle["segment.call"] == pytest.approx(100e-9)
    assert idle["segment"] == pytest.approx(150e-9)
    assert idle["hook"] == pytest.approx(50e-9)
    assert idle["drain"] == pytest.approx(350e-9)
    assert idle["drain.records"] == pytest.approx(150e-9)
    assert idle["other"] == pytest.approx(200e-9)
    assert "unrelated" not in idle and "bench_clock_anchor" not in idle
    assert len(es.idle_by_span(tr, 0.0, 1200.0, top=3)) == 3


def test_module_seconds_reads_the_modules_line():
    mods = es.module_seconds(_trace(HOST, OPS), 0.0, 1200.0)
    assert mods == {"jit_placement_segment": pytest.approx(200e-9),
                    "jit_other": pytest.approx(50e-9)}


READERS = [
    lambda ctx: es.admit_ms_per_wave(ctx),
    lambda ctx: es.ms_per_segment(ctx, "segment"),
    lambda ctx: es.ms_per_segment(ctx, "drain"),
    lambda ctx: es.counter_per_segment(ctx, "d2h_transfers"),
    lambda ctx: es.counter_per_segment(ctx, "h2d_transfers"),
    lambda ctx: es.queue_ms_p50(ctx),
]


@pytest.fixture(scope="module")
def traced_ctx():
    from repro.core.flexai import FlexAIAgent, FlexAIConfig
    from repro.core.hmai import HMAIPlatform
    from repro.core.tasks import TaskArrays
    from repro.serve.qos import QoSConfig, QoSPlacementEngine
    from repro.serve.tracing import Tracer
    plat = HMAIPlatform(capacity_scale=0.05)
    agent = FlexAIAgent(plat, FlexAIConfig(seed=3))
    eng = QoSPlacementEngine(plat, agent.learner.eval_p,
                             QoSConfig(policy="fifo", slots=2, chunk=8,
                                       min_bucket=16),
                             backlog_scale=agent.cfg.backlog_scale)
    rng = np.random.default_rng(0)
    tr = Tracer()
    eng.tracer = tr
    lo = time.perf_counter_ns()
    for i in range(4):
        n = 12 + i
        eng.submit(TaskArrays(
            kind=rng.integers(0, 3, n).astype(np.int32),
            arrival=np.sort(rng.uniform(0, 0.1, n)).astype(np.float32),
            safety=np.full(n, 0.05, np.float32),
            group=np.zeros(n, np.int32), valid=np.ones(n, bool)))
    eng.run_until_done()
    hi = time.perf_counter_ns()
    eng.tracer = None
    return {"spans": es.window(tr, lo, hi),
            "trace": {"dispatches": eng.dispatches}}


@pytest.mark.parametrize("i", range(len(READERS)))
def test_readers_need_spans(i, traced_ctx):
    assert READERS[i]({"trace": {"dispatches": 10}}) is None
    assert READERS[i]({}) is None
    v = READERS[i](traced_ctx)
    assert isinstance(v, float) and v > 0.0


def test_counter_readers_follow_the_shape_arithmetic(traced_ctx):
    d = traced_ctx["trace"]["dispatches"]
    waves = traced_ctx["spans"]["counters"]["waves_admitted"]
    assert es.counter_per_segment(traced_ctx, "h2d_transfers") == 5.0
    assert es.counter_per_segment(traced_ctx, "d2h_transfers") == \
        pytest.approx(10.0 + 11.0 * waves / d)
