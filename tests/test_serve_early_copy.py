"""Record copies to the host started at dispatch (``serve/qos.py``).

``_run_wave`` starts the device-to-host copy of every record leaf a
jitted call returns (``_start_host_copy``) and the drain reads the landed
buffers.  The early copy changes when the record bytes travel, never
which: every executor's served records, summaries and serving digest
equal a run in which the helper is disabled and the drain fetches
everything itself.  With a tracer the record leaves are counted where
their copy starts (``d2h_early``), and ``d2h_transfers`` keeps its
arithmetic: 10 record leaves per call plus 11 state leaves per wave.
"""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.flexai import FlexAIAgent, FlexAIConfig
from repro.core.hmai import HMAIPlatform
from repro.core.platform_jax import PlatformState, StepRecord
from repro.core.tasks import TaskArrays
from repro.serve import qos
from repro.serve.durability import (DurableQoSEngine, digests_equal,
                                    serving_digest)
from repro.serve.qos import QoSConfig, QoSPlacementEngine
from repro.serve.tracing import Tracer

RS = 0.05
_PLATFORM = HMAIPlatform(capacity_scale=RS)
_AGENT = FlexAIAgent(_PLATFORM, FlexAIConfig(seed=3))
_PIPE = []

MODES = {
    "drain": dict(policy="fifo", slots=2, chunk=8, min_bucket=16),
    "continuous": dict(policy="edf", slots=2, chunk=8, min_bucket=16,
                       continuous=True),
    "preempt": dict(policy="edf", slots=2, chunk=8, min_bucket=16,
                    laxity_s=1e-4, aging_credit=0.0, shed=False),
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
    if stages == 1:
        return _AGENT.learner.eval_p, _AGENT.cfg.backlog_scale
    if not _PIPE:
        from repro.core.pipeline import PipelineFlexAI
        _PIPE.append(PipelineFlexAI(_PLATFORM, FlexAIConfig(
            min_replay=32, batch_size=16, update_every=2,
            eps_decay_steps=500, replay_capacity=2048, seed=2), n_stages=2))
    return _PIPE[0].eval_params(), _PIPE[0].cfg.backlog_scale


def _watch(eng) -> dict:
    """uid -> (summary, lane final state, lane records) as completed."""
    got = {}
    finish = eng._finish

    def watched(req, summ, lane_final, lane_recs):
        got[req.uid] = (summ, lane_final, lane_recs)
        finish(req, summ, lane_final, lane_recs)

    eng._finish = watched
    return got


def _bytes(x):
    a = np.asarray(x)
    return a.dtype.str, a.shape, a.tobytes()


def _assert_same_records(a: dict, b: dict) -> None:
    assert a and a.keys() == b.keys()
    for uid in a:
        (sa, fa, ra), (sb, fb, rb) = a[uid], b[uid]
        assert type(ra) is type(rb) and type(fa) is type(fb)
        for x, y in zip(jax.tree_util.tree_leaves((fa, ra)),
                        jax.tree_util.tree_leaves((fb, rb))):
            assert _bytes(x) == _bytes(y)
        assert sa.keys() == sb.keys()
        for k in sa:
            assert _bytes(sa[k]) == _bytes(sb[k]), k


def _serve(mode: str, tracer=None, executor=None):
    cfg = QoSConfig(**MODES[mode])
    params, scale = _params(cfg.stages)
    eng = QoSPlacementEngine(_PLATFORM, params, cfg, backlog_scale=scale,
                             executor=executor)
    eng.tracer = tracer
    got = _watch(eng)
    if mode == "preempt":
        # a long slack route starts first; tighter ones arrive mid-wave
        # and preempt it at a segment cut
        eng.submit(_route(60, 0), arrival=0.0, deadline=1e6)
        for i in range(1, 4):
            eng.submit(_route(12 + 3 * i, i), arrival=1e-4 * i,
                       deadline=0.05 + 0.01 * i)
    else:
        for i in range(5):
            eng.submit(_route(10 + 3 * i, i), arrival=0.001 * i,
                       deadline=100.0)
    eng.run_until_done()
    return eng, got


def _no_early(m) -> None:
    """Turn the early copy off: the drain fetches every record itself."""
    m.setattr(qos, "_start_host_copy", lambda tree: 0)


@pytest.mark.parametrize("mode", sorted(MODES))
def test_early_copy_serves_the_same_bytes(mode, monkeypatch):
    on, rec_on = _serve(mode)
    with monkeypatch.context() as m:
        _no_early(m)
        off, rec_off = _serve(mode)
    assert on.stats() == off.stats()
    assert digests_equal(serving_digest(on), serving_digest(off))
    _assert_same_records(rec_on, rec_off)
    assert len(rec_on) == len(on.completed) > 0
    if mode == "preempt":
        assert on.preemption_count > 0
    if mode == "continuous":
        assert on.refills > 0


def test_every_dispatch_starts_the_copy_of_what_the_seam_returned(
        monkeypatch):
    """One start per jitted call, on the records ``_dispatch_segment``
    returned: executors swapped in at that seam get it unchanged."""
    started, returned = [], []
    real = qos._start_host_copy

    def spy(tree):
        started.append(tree)
        return real(tree)

    monkeypatch.setattr(qos, "_start_host_copy", spy)
    eng = QoSPlacementEngine(_PLATFORM, _AGENT.learner.eval_p,
                             QoSConfig(**MODES["drain"]),
                             backlog_scale=_AGENT.cfg.backlog_scale)
    seam = eng._dispatch_segment

    def dispatch(wave, seg):
        out = seam(wave, seg)
        returned.append(out[1])
        return out

    eng._dispatch_segment = dispatch
    eng.tracer = Tracer()
    for i in range(3):
        eng.submit(_route(20 + i, i), deadline=100.0)
    eng.run_until_done()
    calls = eng.tracer.counters["segment_calls"]
    assert len(started) == len(returned) == calls > 0
    assert all(s is r for s, r in zip(started, returned))
    assert all(isinstance(x, jax.Array)
               for r in returned for x in jax.tree_util.tree_leaves(r))


def test_start_host_copy_counts_device_leaves_only():
    dev = jnp.arange(6, dtype=jnp.float32).reshape(2, 3)
    host = np.ones((2, 3), np.int32)
    tree = StepRecord(*([dev] * 5 + [host] * 5))
    assert qos._start_host_copy(tree) == 5
    assert qos._start_host_copy([host, {"a": host}]) == 0
    np.testing.assert_array_equal(np.asarray(dev),
                                  np.arange(6).reshape(2, 3))


@pytest.mark.parametrize("mode", ["continuous", "drain", "pipeline"])
def test_d2h_early_counts_every_record_leaf_at_dispatch(mode):
    tr = Tracer()
    eng, _ = _serve(mode, tr)
    c = tr.summary()["counters"]
    waves = len(eng.wave_log)
    n_rec = len(StepRecord._fields)
    calls = c["segment_calls"]
    assert c["d2h_early"] == n_rec * calls > 0
    assert c["d2h_transfers"] == (n_rec * calls
                                  + len(PlatformState._fields) * waves)
    # each start is logged inside a segment span, none inside a drain
    segs = [(sp.start_ns, sp.end_ns) for sp in tr.spans
            if sp.name == "segment"]
    drains = [(sp.start_ns, sp.end_ns) for sp in tr.spans
              if sp.name == "drain"]
    early = [t for t, name, _ in tr._log if name == "d2h_early"]
    assert len(early) == calls
    assert all(any(a <= t <= b for a, b in segs) for t in early)
    assert not any(a <= t <= b for a, b in drains for t in early)


def test_stub_records_are_host_arrays_and_start_nothing():
    tr = Tracer()
    eng, got = _serve("drain", tr, executor="stub")
    c = tr.summary()["counters"]
    assert eng.stats()["completed"] == 5 == len(got)
    assert c.get("d2h_early", 0) == 0
    # the stub's records are NumPy: only the state's leaves travel
    assert c["d2h_transfers"] == len(PlatformState._fields) * len(
        eng.wave_log)


def _durable(snap_dir=None):
    cfg = QoSConfig(policy="edf", slots=2, chunk=16, min_bucket=16)
    kw = {} if snap_dir is None else dict(snapshot_dir=str(snap_dir),
                                          snapshot_every=3)
    return DurableQoSEngine(_PLATFORM, _AGENT.learner.eval_p, cfg,
                            backlog_scale=_AGENT.cfg.backlog_scale, **kw)


def _submit(eng, n_req=4, seed=0):
    rng = np.random.default_rng(seed)
    t = 0.0
    for i in range(n_req):
        eng.submit(_route(int(rng.integers(40, 90)), seed + 10 * i),
                   arrival=t)
        t += float(rng.uniform(0.0, eng.base_svc * 16))


def _crash_and_restore(snap_dir):
    """Serve two waves with cadence snapshots, restore the latest (taken
    mid-wave) and finish: the digest and every completed lane's records."""
    crashed = _durable(snap_dir)
    got = _watch(crashed)
    _submit(crashed)
    crashed.serve_waves(2)
    crashed.saver.wait()
    assert crashed.snapshots_written > 0
    restored = DurableQoSEngine.restore(
        str(snap_dir), _PLATFORM, backlog_scale=_AGENT.cfg.backlog_scale)
    assert restored._inflight is not None
    got.update(_watch(restored))
    restored.run_until_done()
    restored.saver.wait()
    return serving_digest(restored), got


def test_durable_saver_and_mid_wave_restore_serve_the_same_bytes(
        tmp_path, monkeypatch):
    ref = _durable()
    _submit(ref)
    ref.run_until_done()
    dig_on, rec_on = _crash_and_restore(tmp_path / "on")
    with monkeypatch.context() as m:
        _no_early(m)
        dig_off, rec_off = _crash_and_restore(tmp_path / "off")
    assert digests_equal(dig_on, dig_off)
    assert digests_equal(serving_digest(ref), dig_on)
    _assert_same_records(rec_on, rec_off)


_MESH_SCRIPT = textwrap.dedent("""
    import jax
    import numpy as np
    from repro.compat import make_mesh
    from repro.core.flexai import FlexAIAgent, FlexAIConfig
    from repro.core.hmai import HMAIPlatform
    from repro.core.tasks import TaskArrays
    from repro.serve import qos
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

    def serve():
        # three lanes on two devices: padded to four, trimmed back to three
        eng = QoSPlacementEngine(
            plat, agent.learner.eval_p,
            QoSConfig(policy="fifo", slots=3, chunk=8, min_bucket=16),
            backlog_scale=agent.cfg.backlog_scale, mesh=mesh)
        eng.tracer = Tracer()
        recs = {}
        finish = eng._finish
        def watched(req, summ, lane_final, lane_recs):
            recs[req.uid] = [np.asarray(x).tobytes() for x in
                             jax.tree_util.tree_leaves((lane_final,
                                                        lane_recs))]
            finish(req, summ, lane_final, lane_recs)
        eng._finish = watched
        for i in range(5):
            eng.submit(route(10 + 3 * i, i), deadline=100.0)
        eng.run_until_done()
        return eng, recs

    on, rec_on = serve()
    real = qos._start_host_copy
    qos._start_host_copy = lambda tree: 0
    off, rec_off = serve()
    qos._start_host_copy = real
    assert digests_equal(serving_digest(on), serving_digest(off))
    assert rec_on == rec_off and len(rec_on) == 5
    c = on.tracer.summary()["counters"]
    early, calls = c["d2h_early"], c["segment_calls"]
    assert early == 10 * calls > 0, (early, calls)
    print("OK", on.dispatches)
""")


def test_early_copy_on_a_two_device_mesh():
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
