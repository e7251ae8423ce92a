"""Many segments a jitted call (``QoSPlacementEngine._segments_per_call``).

A segment boundary exists only where the host may act on it.  Where
none does — a FIFO drain wave, or EDF without preemption — a jitted
call serves half the wave's segments, so the wave takes two calls and
the second resumes from the state the first handed back.  The scan body
does not depend on the trip count, so such an engine serves the same
bytes as one whose calls serve one segment each: the same placements,
final states, summaries, completion order, virtual clock and stats,
with ``dispatches`` still counting ``chunk``-task segments.  Engines
whose host acts at every boundary (EDF preemption, continuous batching,
the measured service clock, pipeline waves and the durable engine) keep
one segment a call.
"""
import os
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest

from repro.core.flexai import FlexAIAgent, FlexAIConfig
from repro.core.hmai import HMAIPlatform
from repro.core.tasks import TaskArrays
from repro.serve.durability import (DurableQoSEngine, digests_equal,
                                    serving_digest)
from repro.serve.qos import QoSConfig, QoSPlacementEngine
from repro.serve.tracing import Tracer

_PLATFORM = HMAIPlatform(capacity_scale=0.05)
_AGENT = FlexAIAgent(_PLATFORM, FlexAIConfig(seed=3))
_PIPE = []


def _route(n: int, seed: int) -> TaskArrays:
    rng = np.random.default_rng(seed)
    return TaskArrays(
        kind=rng.integers(0, 3, n).astype(np.int32),
        arrival=np.sort(rng.uniform(0, 0.01 * n, n)).astype(np.float32),
        safety=np.full(n, 0.05, np.float32),
        group=np.zeros(n, np.int32),
        valid=np.ones(n, bool))


class Recorded(QoSPlacementEngine):
    """Keeps every completion's bytes, in completion order, and in a
    closed backlog submits the next route as each one completes."""

    def setup(self, routes=(), total=0):
        self.got = []
        self.refill_routes = list(routes)
        self.refill_total = total

    def _on_complete(self, req, lane_final, lane_recs):
        leaves = jax.tree_util.tree_leaves((lane_final, lane_recs))
        self.got.append((req.uid, [np.asarray(x).tobytes()
                                   for x in leaves]))
        if self._order < self.refill_total:
            self.submit(self.refill_routes[self._order
                                           % len(self.refill_routes)],
                        arrival=self.now)


class OneSegment(Recorded):
    """The same engine, with one segment per jitted call."""

    def _segments_per_call(self, wave):
        return 1


def _mixed(eng):
    """Seeded mixed buckets (16 to 128 tasks), arriving over time, so
    arrivals are promoted between the segments of a fused call."""
    rng = np.random.default_rng(7)
    t = 0.0
    for i in range(14):
        n = int(rng.integers(9, 120))
        eng.submit(_route(n, 100 + i), arrival=t)
        t += float(rng.uniform(0.0, 0.5))


def _closed(eng):
    """A closed backlog: each completion submits the next route."""
    routes = [_route(n, 200 + i) for i, n in enumerate((12, 40, 23, 70, 31))]
    eng.setup(routes, total=16)
    for r in routes[:4]:
        eng.submit(r)


SPLIT_CALLS = 5


def _resumed(eng):
    """A FIFO wave that served its first segments one call each is
    checkpointed by an outside caller; resumed, its next call serves the
    rest of the wave, and the drain joins records of unequal calls."""
    for i, n in enumerate((50, 61, 33)):
        eng.submit(_route(n, 300 + i))
    wave = eng._next_wave()
    eng._segments_per_call = lambda w: 1
    for _ in range(SPLIT_CALLS):
        eng._serve_segment(wave)
    del eng._segments_per_call
    eng.preempted.append(wave)


SCENARIOS = {
    "mixed_fifo": (dict(policy="fifo"), _mixed),
    "mixed_edf_no_preempt": (dict(policy="edf", preempt=False), _mixed),
    "closed_backlog_fifo": (dict(policy="fifo"), _closed),
    "resumed_fifo": (dict(policy="fifo"), _resumed),
}


def _serve(cls, name):
    kw, load = SCENARIOS[name]
    # a service time whose sums round: a call that charged its k
    # segments as one product would move the clock's last bits
    eng = cls(_PLATFORM, _AGENT.learner.eval_p,
              QoSConfig(slots=3, chunk=8, min_bucket=16,
                        svc_per_task=1e-3 / 3, **kw),
              backlog_scale=_AGENT.cfg.backlog_scale)
    eng.setup()
    eng.tracer = Tracer()
    load(eng)
    eng.run_until_done()
    return eng


def _summary_bytes(eng):
    return {r.uid: {k: np.asarray(v).tobytes()
                    for k, v in r.summary.items()} for r in eng.completed}


@pytest.mark.parametrize("name", sorted(SCENARIOS))
def test_fused_waves_serve_the_same_bytes(name):
    fused, one = _serve(Recorded, name), _serve(OneSegment, name)
    assert fused.got and fused.got == one.got
    assert [r.uid for r in fused.completed] == [r.uid for r in one.completed]
    assert digests_equal(serving_digest(fused), serving_digest(one))
    assert _summary_bytes(fused) == _summary_bytes(one)
    assert fused.now == one.now
    assert fused.stats() == one.stats()
    assert fused.dispatches == one.dispatches > len(fused.wave_log)
    cf, co = fused.tracer.counters, one.tracer.counters
    assert co["segment_calls"] == one.dispatches
    assert cf["waves_admitted"] == len(fused.wave_log)
    if name == "resumed_fifo":
        # the split calls, then one for the rest: fewer than half the
        # wave's segments are left
        assert cf["segment_calls"] == SPLIT_CALLS + 1
    else:
        # two calls per admission round, each of half the wave
        assert cf["segment_calls"] == 2 * cf["waves_admitted"]
    assert cf["d2h_bytes"] == co["d2h_bytes"]
    assert cf["h2d_bytes"] == co["h2d_bytes"]


def _state_unchanged(seg):
    def run(params, tasks, state):
        _, recs = seg(params, tasks, state)
        return state, recs
    return run


def test_a_call_that_hands_back_its_input_state_moves_the_placements(
        monkeypatch):
    """The second call of a wave resumes from the first call's state, so
    a segment function that drops the state it computed serves other
    placements than the sound one — a fault the replay of placements
    sees, and one call a wave would have hidden in the summary."""
    from repro.serve import qos
    sound = _serve(Recorded, "mixed_fifo")
    real = qos._segment_fn
    monkeypatch.setattr(qos, "_segment_fn",
                        lambda *a, **k: _state_unchanged(real(*a, **k)))
    broken = _serve(Recorded, "mixed_fifo")
    placed = [r.summary["placements"].tobytes() for r in sound.completed]
    assert placed != [r.summary["placements"].tobytes()
                      for r in broken.completed]


def _pipeline_params():
    if not _PIPE:
        from repro.core.pipeline import PipelineFlexAI
        _PIPE.append(PipelineFlexAI(_PLATFORM, FlexAIConfig(
            min_replay=32, batch_size=16, update_every=2,
            eps_decay_steps=500, replay_capacity=2048, seed=2), n_stages=2))
    return _PIPE[0].eval_params(), _PIPE[0].cfg.backlog_scale


SPLIT = {
    "edf_preempt": dict(policy="edf", laxity_s=1e-4, aging_credit=0.0,
                        shed=False),
    "continuous": dict(policy="edf", continuous=True),
    "measured_svc": dict(policy="fifo", measured_svc=True),
    "pipeline": dict(policy="edf", stages=2),
    "durable": dict(policy="fifo"),
}


@pytest.mark.parametrize("name", sorted(SPLIT))
def test_engines_the_host_acts_on_keep_one_segment_a_call(name):
    cfg = QoSConfig(slots=2, chunk=8, min_bucket=16, **SPLIT[name])
    if name == "pipeline":
        params, scale = _pipeline_params()
    else:
        params, scale = _AGENT.learner.eval_p, _AGENT.cfg.backlog_scale
    cls = DurableQoSEngine if name == "durable" else QoSPlacementEngine
    eng = cls(_PLATFORM, params, cfg, backlog_scale=scale)
    eng.tracer = tr = Tracer()
    if name == "edf_preempt":
        # a long slack route starts first; tighter ones arrive mid-wave
        eng.submit(_route(60, 0), arrival=0.0, deadline=1e6)
        for i in range(1, 4):
            eng.submit(_route(12 + 3 * i, i), arrival=1e-4 * i,
                       deadline=0.05 + 0.01 * i)
    else:
        for i in range(5):
            eng.submit(_route(10 + 9 * i, i), arrival=0.001 * i,
                       deadline=100.0)
    eng.run_until_done()
    assert eng.completed
    assert tr.counters["segment_calls"] == eng.dispatches \
        > len(eng.wave_log)
    if name == "edf_preempt":
        assert eng.preemption_count > 0


_MESH_SCRIPT = textwrap.dedent("""
    import jax
    import numpy as np
    from repro.compat import make_mesh
    from repro.core.flexai import FlexAIAgent, FlexAIConfig
    from repro.core.hmai import HMAIPlatform
    from repro.core.tasks import TaskArrays
    from repro.serve.durability import digests_equal, serving_digest
    from repro.serve.qos import QoSConfig, QoSPlacementEngine
    from repro.serve.tracing import Tracer

    assert len(jax.devices()) == 2
    plat = HMAIPlatform(capacity_scale=0.05)
    agent = FlexAIAgent(plat, FlexAIConfig(seed=3))
    mesh = make_mesh((2,), ("routes",))

    def route(n, seed):
        rng = np.random.default_rng(seed)
        return TaskArrays(
            kind=rng.integers(0, 3, n).astype(np.int32),
            arrival=np.sort(rng.uniform(0, 0.01 * n, n)).astype(np.float32),
            safety=np.full(n, 0.05, np.float32),
            group=np.zeros(n, np.int32), valid=np.ones(n, bool))

    class OneSegment(QoSPlacementEngine):
        def _segments_per_call(self, wave):
            return 1

    def serve(cls):
        # three lanes on two devices: padded to four, trimmed back to three
        eng = cls(plat, agent.learner.eval_p,
                  QoSConfig(policy="fifo", slots=3, chunk=8, min_bucket=16),
                  backlog_scale=agent.cfg.backlog_scale, mesh=mesh)
        eng.tracer = Tracer()
        recs = []
        finish = eng._finish
        def watched(req, summ, lane_final, lane_recs):
            recs.append((req.uid, [np.asarray(x).tobytes() for x in
                                   jax.tree_util.tree_leaves((lane_final,
                                                              lane_recs))]))
            finish(req, summ, lane_final, lane_recs)
        eng._finish = watched
        for i in range(7):
            eng.submit(route(10 + 9 * i, i), deadline=100.0)
        eng.run_until_done()
        return eng, recs

    fused, rec_f = serve(QoSPlacementEngine)
    one, rec_o = serve(OneSegment)
    assert digests_equal(serving_digest(fused), serving_digest(one))
    assert rec_f == rec_o and len(rec_f) == 7
    assert fused.stats() == one.stats()
    cf, co = fused.tracer.counters, one.tracer.counters
    assert cf["segment_calls"] == 2 * cf["waves_admitted"] \
        == 2 * len(fused.wave_log)
    assert co["segment_calls"] == one.dispatches == fused.dispatches
    assert fused.dispatches > len(fused.wave_log)
    print("OK", fused.dispatches, cf["segment_calls"])
""")


def test_fused_waves_on_a_two_device_mesh():
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    env["JAX_PLATFORMS"] = "cpu"
    src = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src"))
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    out = subprocess.run([sys.executable, "-c", _MESH_SCRIPT], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "OK" in out.stdout
