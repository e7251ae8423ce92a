"""A fresh wave's checkpoint is one constant per engine (serve/qos.py).

``QoSPlacementEngine`` builds the stacked ``PlatformState`` (and, for
pipeline waves, the ring) a fresh wave starts from once, at
construction, and ``_pack_wave`` hands every fresh wave those same
arrays.  Nothing donates or writes into a state leaf, so the served
bytes are the ones the per-wave rebuild gave: placements, records,
summaries, stats and the serving digest are identical with the rebuild
put back, in drain, preemptive EDF, pipeline and durable
snapshot/restore serving, and the constant itself is never altered.
"""
import numpy as np
import pytest

from repro.core.flexai import FlexAIAgent, FlexAIConfig
from repro.core.hmai import HMAIPlatform
from repro.core.platform_jax import platform_init, stack_states
from repro.core.tasks import TaskArrays
from repro.serve import qos
from repro.serve.durability import (DurableQoSEngine, digests_equal,
                                    serving_digest)
from repro.serve.qos import QoSConfig, QoSPlacementEngine
from repro.serve.tracing import Tracer

RS = 0.05
_PLATFORM = HMAIPlatform(capacity_scale=RS)
_AGENT = FlexAIAgent(_PLATFORM, FlexAIConfig(seed=3))
_PIPE = None

MODES = ["drain", "edf_preempt", "pipeline", "durable_restore"]
CFGS = {
    "drain": dict(policy="fifo", slots=2, chunk=8, min_bucket=16),
    "continuous": dict(policy="edf", slots=2, chunk=8, min_bucket=16,
                       continuous=True),
    "edf_preempt": dict(policy="edf", slots=2, chunk=8, min_bucket=16,
                        laxity_s=1e-4, aging_credit=0.0),
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


def _engine(mode: str, tracer=None) -> QoSPlacementEngine:
    cfg = QoSConfig(**CFGS[mode])
    params, scale = _params(cfg.stages)
    eng = QoSPlacementEngine(_PLATFORM, params, cfg, backlog_scale=scale)
    eng.tracer = tracer
    return eng


def _submit_preempting(eng: QoSPlacementEngine) -> None:
    """A long route, then a tight short one arriving mid-wave: EDF
    checkpoints the long wave and resumes it after the short one."""
    service_long = eng._bucket(64) * eng.svc
    eng.submit(_route(64, 0), arrival=0.0, deadline=10.0 + service_long)
    for i, frac in enumerate((0.3, 0.55)):
        arrive = frac * service_long
        eng.submit(_route(8, 1 + i), arrival=arrive,
                   deadline=arrive + eng._bucket(8) * eng.svc
                   + 3 * eng.cfg.chunk * eng.svc)


def _serve(mode: str, tracer=None, n: int = 6) -> QoSPlacementEngine:
    eng = _engine(mode, tracer)
    if mode == "edf_preempt":
        _submit_preempting(eng)
    else:
        for i in range(n):
            eng.submit(_route(10 + 7 * i, i), arrival=0.001 * i,
                       deadline=100.0)
    eng.run_until_done()
    return eng


def _durable(**kw) -> DurableQoSEngine:
    return DurableQoSEngine(
        _PLATFORM, _AGENT.learner.eval_p,
        QoSConfig(policy="edf", slots=2, chunk=16, min_bucket=16),
        backlog_scale=_AGENT.cfg.backlog_scale, **kw)


def _serve_durable(tmp_path) -> list:
    """Serve two rounds with a snapshot every third segment, stop there
    (a crash mid-wave), restore from disk and finish."""
    crashed = _durable(snapshot_dir=str(tmp_path), snapshot_every=3)
    rng = np.random.default_rng(0)
    t = 0.0
    for i in range(6):
        crashed.submit(_route(int(rng.integers(40, 90)), 10 * i), arrival=t,
                       deadline=100.0)
        t += float(rng.uniform(0.0, crashed.base_svc * 16))
    crashed.serve_waves(2)
    crashed.saver.wait()
    assert crashed.snapshots_written > 0
    restored = DurableQoSEngine.restore(
        str(tmp_path), _PLATFORM, backlog_scale=_AGENT.cfg.backlog_scale)
    assert restored._inflight is not None
    restored.run_until_done()
    restored.saver.wait()
    assert len(restored.wave_log) >= 3
    return [crashed, restored]


def _rebuild_per_wave(monkeypatch) -> None:
    """Put the per-wave rebuild back: every read of the engine's fresh
    checkpoint builds it anew, as ``_pack_wave`` did for each wave."""
    def state(self):
        return stack_states(
            [platform_init(self.spec.n) for _ in range(self.cfg.slots)])

    def ring(self):
        if self.cfg.stages == 1:
            return None
        import jax.numpy as jnp
        return jnp.zeros((self.cfg.slots, self.cfg.stages), jnp.float32)

    ignore = lambda self, value: None  # noqa: E731
    monkeypatch.setattr(QoSPlacementEngine, "_fresh_state",
                        property(state, ignore), raising=False)
    monkeypatch.setattr(QoSPlacementEngine, "_fresh_ring",
                        property(ring, ignore), raising=False)


def _record_finishes(monkeypatch) -> list:
    """Every completion's uid, summary and lane records, in order."""
    seen = []
    finish = QoSPlacementEngine._finish

    def watched(self, req, summ, lane_final, lane_recs):
        seen.append((req.uid, summ, [np.asarray(x) for x in lane_recs]))
        return finish(self, req, summ, lane_final, lane_recs)

    monkeypatch.setattr(QoSPlacementEngine, "_finish", watched)
    return seen


def _run(mode: str, tmp_path, monkeypatch, rebuild: bool):
    with monkeypatch.context() as m:
        if rebuild:
            _rebuild_per_wave(m)
        seen = _record_finishes(m)
        if mode == "durable_restore":
            engines = _serve_durable(tmp_path / ("old" if rebuild else "new"))
        else:
            engines = [_serve(mode)]
    return engines, seen


def _stats(eng) -> dict:
    """``stats()`` less the wall-clock seconds the snapshots took."""
    s = eng.stats()
    s.pop("snapshot_time_s", None)
    return s


def _assert_same(a, b) -> None:
    if isinstance(a, dict):
        assert set(a) == set(b)
        for k in a:
            _assert_same(a[k], b[k])
    else:
        x, y = np.asarray(a), np.asarray(b)
        assert x.dtype == y.dtype and x.shape == y.shape
        np.testing.assert_array_equal(x, y)


@pytest.mark.parametrize("mode", MODES)
def test_constant_checkpoint_serves_the_same_bytes(mode, tmp_path,
                                                   monkeypatch):
    new, new_seen = _run(mode, tmp_path, monkeypatch, rebuild=False)
    old, old_seen = _run(mode, tmp_path, monkeypatch, rebuild=True)
    if mode == "edf_preempt":
        assert new[0].preemption_count >= 1
    assert _stats(new[-1]) == _stats(old[-1])
    assert _stats(new[-1])["completed"] > 0
    for e_new, e_old in zip(new, old):
        assert digests_equal(serving_digest(e_new), serving_digest(e_old))
    assert [u for u, _, _ in new_seen] == [u for u, _, _ in old_seen]
    for (_, s_new, r_new), (_, s_old, r_old) in zip(new_seen, old_seen):
        _assert_same(s_new, s_old)
        assert len(r_new) == len(r_old)
        for x, y in zip(r_new, r_old):
            _assert_same(x, y)


@pytest.mark.parametrize("mode", ["drain", "continuous", "edf_preempt",
                                  "pipeline"])
def test_constant_is_never_written(mode):
    eng = _serve(mode, n=12)
    assert len(eng.wave_log) >= 3
    want = stack_states([platform_init(eng.spec.n)] * eng.cfg.slots)
    assert type(eng._fresh_state) is type(want)
    for got, ref in zip(eng._fresh_state, want):
        assert got.dtype == ref.dtype and got.shape == ref.shape
        np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))
    if eng.cfg.stages > 1:
        ring = np.asarray(eng._fresh_ring)
        assert ring.dtype == np.float32
        assert ring.shape == (eng.cfg.slots, eng.cfg.stages)
        assert not ring.any()
    else:
        assert eng._fresh_ring is None


def test_fresh_waves_take_the_engine_constant():
    eng = _engine("drain")
    eng.submit(_route(12, 0), deadline=100.0)
    wave = eng._next_wave()
    assert wave.state is eng._fresh_state and wave.ring is None


def test_platform_init_runs_once_per_engine(monkeypatch):
    calls = []
    real = qos.platform_init

    def counted(n):
        calls.append(n)
        return real(n)

    monkeypatch.setattr(qos, "platform_init", counted)
    eng = _engine("drain")
    assert len(calls) == 1
    for i in range(20):
        eng.submit(_route(12, i), deadline=100.0)
    eng.run_until_done()
    assert len(eng.wave_log) == 10 and eng.stats()["completed"] == 20
    assert calls == [eng.spec.n]


@pytest.mark.parametrize("mode", ["drain", "edf_preempt"])
def test_fresh_state_reuses_count_fresh_waves(mode):
    tr = Tracer()
    eng = _serve(mode, tr)
    c = tr.summary()["counters"]
    assert c["waves_admitted"] == len(eng.wave_log)
    # each preemption is resumed once, at its checkpoint, not fresh
    assert c["fresh_state_reuses"] == (c["waves_admitted"]
                                       - eng.preemption_count)
    if mode == "drain":
        assert eng.preemption_count == 0
        assert c["fresh_state_reuses"] == c["waves_admitted"]
    else:
        assert eng.preemption_count >= 1
    init = [sp for sp in tr.spans if sp.name == "admit.init_state"]
    assert len(init) == c["fresh_state_reuses"]
