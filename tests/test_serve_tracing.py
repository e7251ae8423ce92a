"""Spans and transfer counters of the QoS serving loop (serve/tracing.py).

The tracer observes and never steers: placements, stats and the serving
digest are the same with it on and off.  Off, the loop pays no clock read
and builds no annotation.  On, the span tree is well formed and the
transfer counters equal the shape arithmetic of the waves served.
"""
import gc
import time

import jax
import numpy as np
import pytest

from repro.core.flexai import FlexAIAgent, FlexAIConfig
from repro.core.hmai import HMAIPlatform
from repro.core.platform_jax import PlatformState, StepRecord, platform_init
from repro.core.tasks import TaskArrays
from repro.serve.durability import (DurableQoSEngine, digests_equal,
                                    serving_digest)
from repro.serve.qos import QoSConfig, QoSPlacementEngine
from repro.serve.tracing import Tracer

RS = 0.05
_PLATFORM = HMAIPlatform(capacity_scale=RS)
_AGENT = FlexAIAgent(_PLATFORM, FlexAIConfig(seed=3))
_PIPE = None

MODES = {
    "drain": dict(policy="fifo", slots=2, chunk=8, min_bucket=16),
    "continuous": dict(policy="edf", slots=2, chunk=8, min_bucket=16,
                       continuous=True),
    "pipeline": dict(policy="edf", slots=2, chunk=8, min_bucket=16,
                     stages=2),
}


def _route(n: int, seed: int) -> TaskArrays:
    rng = np.random.default_rng(seed)
    return TaskArrays(
        kind=rng.integers(0, 3, n).astype(np.int32),
        arrival=np.sort(rng.uniform(0, 0.01 * n, n)).astype(np.float32),
        safety=np.full(n, 0.05, np.float32),
        group=np.zeros(n, np.int32),
        valid=np.ones(n, bool))


def _params(stages: int):
    global _PIPE
    if stages == 1:
        return _AGENT.learner.eval_p, _AGENT.cfg.backlog_scale
    if _PIPE is None:
        from repro.core.pipeline import PipelineFlexAI
        _PIPE = PipelineFlexAI(_PLATFORM, FlexAIConfig(
            min_replay=32, batch_size=16, update_every=2,
            eps_decay_steps=500, replay_capacity=2048, seed=2), n_stages=2)
    return _PIPE.eval_params(), _PIPE.cfg.backlog_scale


def _serve(mode: str, tracer=None, n: int = 5) -> QoSPlacementEngine:
    cfg = QoSConfig(**MODES[mode])
    params, scale = _params(cfg.stages)
    eng = QoSPlacementEngine(_PLATFORM, params, cfg, backlog_scale=scale)
    eng.tracer = tracer
    for i in range(n):
        eng.submit(_route(10 + 3 * i, i), arrival=0.001 * i, deadline=100.0)
    eng.run_until_done()
    return eng


@pytest.mark.parametrize("mode", sorted(MODES))
def test_tracer_changes_no_outcome(mode):
    off, on = _serve(mode), _serve(mode, Tracer())
    assert off.stats() == on.stats()
    assert digests_equal(serving_digest(off), serving_digest(on))
    pl_off = {r.uid: r.summary["placements"] for r in off.completed}
    for r in on.completed:
        np.testing.assert_array_equal(r.summary["placements"],
                                      pl_off[r.uid])
    assert on.tracer.spans and not off.tracer


def test_off_reads_no_clock_and_builds_no_annotation(monkeypatch):
    eng = _serve("drain", n=1)           # compiled, so the run below is warm
    built, clock_reads, inside = [], [], [False]
    real_clock = time.perf_counter_ns

    class Annotation:
        def __init__(self, *a, **k):
            built.append(a)

    def clock():
        if inside[0]:
            clock_reads.append(1)
        return real_clock()

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Annotation)
    monkeypatch.setattr(time, "perf_counter_ns", clock)
    run_wave = eng._run_wave

    def watched(wave):
        inside[0] = True
        try:
            run_wave(wave)
        finally:
            inside[0] = False

    monkeypatch.setattr(eng, "_run_wave", watched)
    for i in range(3):
        eng.submit(_route(12, 10 + i), deadline=100.0)
    eng.run_until_done()
    assert eng.stats()["completed"] == 4
    assert built == [] and clock_reads == []


@pytest.mark.parametrize("mode", sorted(MODES))
def test_span_tree_is_well_formed(mode):
    tr = Tracer()
    eng = _serve(mode, tr)
    spans = tr.spans
    assert all(sp.end_ns is not None and sp.start_ns <= sp.end_ns
               for sp in spans)
    for sp in spans:
        if sp.parent is not None:
            p = spans[sp.parent]
            assert p.start_ns <= sp.start_ns and sp.end_ns <= p.end_ns
            assert sp.wave == p.wave or p.name == "admit" or sp.name == "gc"
    names = [sp.name for sp in spans]
    calls = tr.counters["segment_calls"]
    assert names.count("segment") == calls <= eng.dispatches
    assert names.count("segment.call") == calls
    uids = [u for w in eng.wave_log for u in w]
    assert sorted(sp.uid for sp in spans if sp.name == "queued") == sorted(
        uids)
    for sp in spans:
        if sp.name in ("segment", "drain"):
            assert 0 <= sp.wave < len(eng.wave_log)
        if sp.name == "drain.summarize":
            assert sp.uid in eng.wave_log[sp.wave] or mode == "continuous"
    admitted = [sp for sp in spans if sp.name == "admit"
                and sp.wave is not None]
    assert len(admitted) == len(eng.wave_log)
    if mode != "continuous":
        # one admission and one drain per drained wave
        assert len(admitted) == names.count("drain") == len(eng.wave_log)
    else:
        # lanes that end on one segment boundary drain together
        assert names.count("drain") == len({r.finish for r in eng.completed})
        assert names.count("drain.summarize") == len(eng.completed)
    s = tr.summary()["spans"]
    for name, e in s.items():
        assert e["self_ns"] == e["total_ns"] - sum(e["children"].values())
    assert {"admit.pack_tasks", "admit.init_state", "segment.slice",
            "hook", "drain.records", "drain.state",
            "drain.summarize"} <= set(s)


def test_counters_equal_the_shape_arithmetic():
    tr = Tracer()
    eng = _serve("drain", tr)
    c = tr.summary()["counters"]
    waves = len(eng.wave_log)
    n_rec, n_state = len(StepRecord._fields), len(PlatformState._fields)
    calls = c["segment_calls"]
    assert c["waves_admitted"] == waves
    assert c["d2h_transfers"] == n_rec * calls + n_state * waves
    lanes, chunk, n_acc = eng.cfg.slots, eng.cfg.chunk, eng.spec.n
    rec_bytes = lanes * chunk * sum(
        4 if f not in ("met", "valid") else 1 for f in StepRecord._fields)
    state_bytes = lanes * sum(np.asarray(x).nbytes
                              for x in platform_init(n_acc))
    assert c["d2h_bytes"] == rec_bytes * eng.dispatches + state_bytes * waves
    # the task slice (kind, arrival, safety, group, valid) is uploaded
    # with each call; params and state already live on the device
    assert c["h2d_transfers"] == len(TaskArrays._fields) * calls
    assert c["h2d_bytes"] == lanes * chunk * (4 + 4 + 4 + 4 + 1) \
        * eng.dispatches
    assert tr.counters == c


@pytest.mark.parametrize("mode", sorted(MODES))
def test_request_timestamps_are_ordered(mode):
    eng = _serve(mode, Tracer())
    assert eng.completed
    for r in eng.completed:
        assert r.t_submit <= r.t_admit <= r.t_done
    bare = _serve(mode, None, n=1)
    r = bare.completed[0]
    assert r.t_submit is None and r.t_admit is None and r.t_done is None


def test_summary_counts_self_time_and_counter_deltas():
    tr = Tracer()
    with tr.span("segment", wave=0):
        with tr.span("segment.call"):
            tr.count("h2d_transfers", 5)
        with tr.span("hook"):
            pass
    mid = time.perf_counter_ns()
    with tr.span("drain", wave=0):
        tr.count("h2d_transfers", 2)
    whole = tr.summary()
    seg = whole["spans"]["segment"]
    assert seg["count"] == 1
    assert seg["self_ns"] == seg["total_ns"] - sum(seg["children"].values())
    assert set(seg["children"]) == {"segment.call", "hook"}
    assert tr.spans[1].wave == 0          # inherited from the parent
    assert whole["counters"]["h2d_transfers"] == 7
    late = tr.summary(lo_ns=mid)
    assert set(late["spans"]) == {"drain"}
    assert late["counters"] == {"h2d_transfers": 2}
    assert "segment n=1" in tr.line() and "h2d_transfers=7" in tr.line()


def test_gc_spans_while_attached_only():
    tr = Tracer()
    eng = QoSPlacementEngine(_PLATFORM, _AGENT.learner.eval_p,
                             QoSConfig(**MODES["drain"]),
                             backlog_scale=_AGENT.cfg.backlog_scale)
    eng.tracer = tr
    assert tr._on_gc in gc.callbacks
    with tr.span("segment", wave=0):
        gc.collect()
    got = [sp for sp in tr.spans if sp.name == "gc"]
    assert got and all(tr.spans[sp.parent].name == "segment" for sp in got)
    eng.tracer = None
    assert tr._on_gc not in gc.callbacks
    n = len(tr.spans)
    gc.collect()
    assert len(tr.spans) == n


def test_snapshot_runs_in_a_span(tmp_path):
    tr = Tracer()
    eng = DurableQoSEngine(_PLATFORM, _AGENT.learner.eval_p,
                           QoSConfig(**MODES["drain"]),
                           backlog_scale=_AGENT.cfg.backlog_scale,
                           snapshot_dir=str(tmp_path), snapshot_every=2)
    eng.tracer = tr
    for i in range(3):
        eng.submit(_route(12, i), deadline=100.0)
    eng.run_until_done()
    eng.saver.wait()
    snaps = [sp for sp in tr.spans if sp.name == "snapshot"]
    assert len(snaps) == eng.snapshots_written > 0
    assert all(tr.spans[sp.parent].name == "hook" for sp in snaps)
    assert eng.stats()["snapshot_time_s"] > 0.0


def test_launcher_trace_prints_one_summary_line(capsys):
    from repro.launch import serve
    args = serve.parse_args(["--placement", "--trace", "--routes", "2",
                             "--rate-scale", "0.05", "--slots", "2"])
    eng = serve.run_qos_placement_serving(args)
    out = capsys.readouterr().out.splitlines()
    lines = [ln for ln in out if ln.startswith("spans: ")]
    assert len(lines) == 1
    assert "segment n=" in lines[0] and "d2h_transfers=" in lines[0]
    assert eng.tracer is None and eng.stats()["completed"] == 2
