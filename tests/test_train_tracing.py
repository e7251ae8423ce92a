"""Spans and counters of the fused FlexAI trainer (``ScanFlexAI.tracer``).

The tracer observes and never steers: weights, losses and records are the
same with it on and off.  On, the counters equal what the episodes
returned, the spans nest as ``episode`` > ``episode.upload``,
``episode.call``, ``episode.fetch``, ``episode.summarize`` beside one
``eval`` per eval round, and together they cover the training loop's
wall time.  The jitted episode and eval carry the module names a device
trace attributes their time by.
"""
import time

import jax
import numpy as np
import pytest

from repro.core.flexai import FlexAIConfig, ScanFlexAI
from repro.core.hmai import HMAIPlatform
from repro.core.tasks import TaskArrays
from repro.serve.tracing import Tracer

RS = 0.05
CFG = FlexAIConfig(min_replay=32, batch_size=16, update_every=2,
                   eps_decay_steps=500, replay_capacity=2048,
                   target_sync_every=20, seed=5)
EPISODES, EVAL_EVERY, N = 3, 2, 300
EPISODE_CHILDREN = ("episode.upload", "episode.call", "episode.fetch",
                    "episode.summarize")


def _route(n: int, seed: int) -> TaskArrays:
    rng = np.random.default_rng(seed)
    return TaskArrays(
        kind=rng.integers(0, 3, n).astype(np.int32),
        arrival=np.sort(rng.uniform(0, 0.01 * n, n)).astype(np.float32),
        safety=np.full(n, 0.05, np.float32),
        group=np.zeros(n, np.int32),
        valid=np.ones(n, bool))


ROUTES = [_route(N, 1), _route(N, 2)]
EVAL = _route(N, 9)


def _train(tracer=None):
    trainer = ScanFlexAI(HMAIPlatform(capacity_scale=RS), CFG)
    trainer.tracer = tracer
    outputs = []
    t0 = time.perf_counter_ns()
    history = trainer.train(
        ROUTES, EPISODES, eval_queue=EVAL, eval_every=EVAL_EVERY,
        on_episode=lambda ep, tr: outputs.append(tr.last_episode))
    t1 = time.perf_counter_ns()
    trainer.tracer = None
    return trainer, history, outputs, (t0, t1)


@pytest.fixture(scope="module")
def traced_run():
    tracer = Tracer()
    trainer, history, outputs, window = _train(tracer)
    return tracer, trainer, history, outputs, window


def test_tracer_changes_no_outcome(traced_run):
    _, traced, hist_on, outs_on, _ = traced_run
    plain, hist_off, outs_off, _ = _train()
    assert hist_on == hist_off
    assert traced.losses == plain.losses
    for a, b in zip(jax.tree_util.tree_leaves(jax.device_get(traced.ts)),
                    jax.tree_util.tree_leaves(jax.device_get(plain.ts))):
        np.testing.assert_array_equal(a, b)
    assert len(outs_on) == len(outs_off) == EPISODES
    for on, off in zip(outs_on, outs_off):
        for a, b in zip(jax.tree_util.tree_leaves(on),
                        jax.tree_util.tree_leaves(off)):
            np.testing.assert_array_equal(a, b)


def test_counters_equal_the_episodes_outputs(traced_run):
    tracer, _, _, outputs, _ = traced_run
    c = tracer.summary()["counters"]
    recs = [o[0] for o in outputs]
    assert c["episodes"] == EPISODES
    assert c["train_steps"] == sum(int(r.valid.sum()) for r in recs) \
        == EPISODES * N
    assert c["td_updates"] == sum(int(o[2].sum()) for o in outputs) > 0
    # five task-array leaves up, ten record fields + losses + mask down
    assert c["h2d_transfers"] == 5 * EPISODES
    assert c["d2h_transfers"] == (len(recs[0]) + 2) * EPISODES


def test_spans_nest_and_cover_the_training_loop(traced_run):
    tracer, _, _, _, (t0, t1) = traced_run
    spans = tracer.spans
    names = [sp.name for sp in spans]
    assert names.count("episode") == EPISODES
    assert names.count("eval") == EPISODES // EVAL_EVERY
    top = []
    for sp in spans:
        assert sp.end_ns is not None and sp.start_ns <= sp.end_ns
        if sp.name == "gc":
            continue
        parent = None if sp.parent is None else spans[sp.parent]
        if sp.name in EPISODE_CHILDREN:
            assert parent is not None and parent.name == "episode"
            assert parent.start_ns <= sp.start_ns <= sp.end_ns \
                <= parent.end_ns
        else:
            assert sp.name in ("episode", "eval") and parent is None
            top.append(sp)
    for i, sp in enumerate(spans):
        if sp.name == "episode":
            kids = [k.name for k in spans if k.parent == i
                    and k.name != "gc"]
            assert kids == list(EPISODE_CHILDREN)
    covered = sum(sp.end_ns - sp.start_ns for sp in top)
    assert covered >= 0.95 * (t1 - t0)


def test_jitted_episode_and_eval_carry_their_module_names(traced_run):
    _, trainer, _, _, _ = traced_run
    ta = jax.device_put(ROUTES[0])
    assert "@jit_train_episode" in trainer._train_fn.lower(
        trainer.ts, ta).as_text()
    assert "@jit_eval_episode" in trainer._eval_fn.lower(
        trainer.eval_params(), EVAL).as_text()
