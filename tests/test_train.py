"""Training loop, checkpoint/restore, fault tolerance, data determinism."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models.api import model_api
from repro.models.config import ModelConfig
from repro.sharding import unbox
from repro.train import checkpoint as ckpt
from repro.train.data import DataConfig, batch_fn
from repro.train.fault_tolerance import (PreemptionGuard, StragglerDetector,
                                         HeartbeatRecord, elastic_restore,
                                         run_with_fault_tolerance)
from repro.train.loop import TrainHyper, init_train_state, make_train_step

KEY = jax.random.PRNGKey(11)

CFG = ModelConfig(name="train-tiny", family="dense", num_layers=2, d_model=64,
                  num_heads=4, num_kv_heads=2, d_ff=128, vocab_size=64,
                  attention_impl="naive")


def _setup(compression="none", micro=1):
    import dataclasses
    cfg = dataclasses.replace(CFG, use_grad_accum_microbatches=micro)
    api = model_api(cfg)
    hyper = TrainHyper(peak_lr=3e-3, warmup_steps=5, total_steps=200,
                       compression=compression)
    params = unbox(api.init(KEY))
    state = init_train_state(params, hyper)
    step = jax.jit(make_train_step(api, hyper))
    data = DataConfig(batch_size=4, seq_len=32, seed=1)
    return cfg, state, step, batch_fn(cfg, data)


def test_loss_decreases():
    cfg, state, step, bat = _setup()
    losses = []
    for i in range(40):
        state, m = step(state, bat(i))
        losses.append(float(m["loss"]))
    assert np.mean(losses[-5:]) < np.mean(losses[:5]) - 0.5, losses[:5]


@pytest.mark.parametrize("compression", ["bf16", "int8_ef"])
def test_compressed_training_still_learns(compression):
    cfg, state, step, bat = _setup(compression=compression)
    losses = []
    for i in range(40):
        state, m = step(state, bat(i))
        losses.append(float(m["loss"]))
    assert np.mean(losses[-5:]) < np.mean(losses[:5]) - 0.4


def test_grad_accum_matches_full_batch():
    """2-microbatch grad accumulation == single-batch step (same batch)."""
    _, state1, step1, bat = _setup(micro=1)
    _, state2, step2, _ = _setup(micro=2)
    b = bat(0)
    s1, m1 = step1(state1, b)
    s2, m2 = step2(state2, b)
    np.testing.assert_allclose(float(m1["loss"]), float(m2["loss"]),
                               rtol=2e-2)
    # params should land close (not identical: loss normalization order)
    l1 = jax.tree_util.tree_leaves(s1.params)
    l2 = jax.tree_util.tree_leaves(s2.params)
    for a, b_ in zip(l1, l2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=5e-2, atol=5e-4)


def test_checkpoint_roundtrip(tmp_path):
    cfg, state, step, bat = _setup()
    for i in range(3):
        state, _ = step(state, bat(i))
    path = ckpt.save_checkpoint(str(tmp_path), 3, state)
    assert ckpt.latest_checkpoint(str(tmp_path)) == path
    template = jax.tree_util.tree_map(np.zeros_like, jax.device_get(state))
    restored = ckpt.restore_checkpoint(path, template)
    for a, b in zip(jax.tree_util.tree_leaves(state),
                    jax.tree_util.tree_leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_restart_equals_uninterrupted(tmp_path):
    """Crash at step 12, restore from ckpt, resume -> identical final loss."""
    cfg, state0, step, bat = _setup()

    # uninterrupted
    res_full = run_with_fault_tolerance(
        step, state0, bat, num_steps=20, ckpt_dir=str(tmp_path / "a"),
        ckpt_every=5)

    # interrupted at 12 (checkpoints at 5 and 10)
    _, state_b, step_b, _ = _setup()
    with pytest.raises(RuntimeError):
        run_with_fault_tolerance(
            step_b, state_b, bat, num_steps=20,
            ckpt_dir=str(tmp_path / "b"), ckpt_every=5, fail_at_step=12)
    template = jax.device_get(state_b)
    restored, start = elastic_restore(str(tmp_path / "b"), template)
    assert start == 10
    res_resumed = run_with_fault_tolerance(
        step_b, restored, bat, num_steps=20, ckpt_dir=str(tmp_path / "b"),
        ckpt_every=5, start_step=start)

    for a, b in zip(jax.tree_util.tree_leaves(res_full.final_state.params),
                    jax.tree_util.tree_leaves(res_resumed.final_state.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)


def test_preemption_guard_checkpoints(tmp_path):
    cfg, state, step, bat = _setup()
    guard = PreemptionGuard(install_handler=False)
    guard.preempted = True
    res = run_with_fault_tolerance(
        step, state, bat, num_steps=10, ckpt_dir=str(tmp_path),
        ckpt_every=100, guard=guard)
    assert res.interrupted and res.completed_steps == 0
    assert ckpt.latest_checkpoint(str(tmp_path)) is not None


def test_straggler_detection():
    det = StragglerDetector(n_hosts=4, threshold=1.5, window=8)
    import time
    now = time.time()
    for step in range(8):
        for h in range(4):
            dt = 1.0 if h != 2 else 2.5  # host 2 is slow
            det.record(HeartbeatRecord(h, step, dt, now))
    assert det.stragglers() == [2]
    assert det.dead_hosts(now=now + 120) == [0, 1, 2, 3]
    assert det.dead_hosts(now=now + 1) == []


def test_data_pipeline_determinism():
    cfg = CFG
    data = DataConfig(batch_size=4, seq_len=32, seed=3)
    b1 = batch_fn(cfg, data)(17)
    b2 = batch_fn(cfg, data)(17)
    np.testing.assert_array_equal(b1["tokens"], b2["tokens"])
    b3 = batch_fn(cfg, data)(18)
    assert not np.array_equal(b1["tokens"], b3["tokens"])


# ---------------------------------------------------------------------------
# durability satellites: checkpointer ordering, dtype manifest, clocks
# ---------------------------------------------------------------------------

def test_async_checkpointer_overlapping_saves_keep_order(tmp_path,
                                                         monkeypatch):
    """Overlapping saves must land in submission order and a stale step
    resubmitted while a newer one is in flight must lose — the on-disk
    ``latest_checkpoint`` can never go backwards."""
    import time as _time
    real_write = ckpt._write

    def slow_write(directory, step, names, host):
        _time.sleep(0.05)
        return real_write(directory, step, names, host)

    monkeypatch.setattr(ckpt, "_write", slow_write)
    saver = ckpt.AsyncCheckpointer(str(tmp_path))
    saver.save(1, {"x": np.full(4, 1.0)})
    saver.save(2, {"x": np.full(4, 2.0)})  # overlaps save 1
    saver.save(1, {"x": np.full(4, 9.0)})  # stale resubmit: dropped
    saver.wait()
    path = ckpt.latest_checkpoint(str(tmp_path))
    assert ckpt.checkpoint_step(path) == 2
    _, arrays, _ = ckpt.load_checkpoint_arrays(path)
    np.testing.assert_array_equal(arrays[0], np.full(4, 2.0))
    # both steps were written, in order (step 1 not clobbered by the
    # stale resubmit, step 2 newest)
    assert ckpt.checkpoint_step(os.path.join(
        str(tmp_path), "step_00000001")) == 1


def test_async_checkpointer_callable_state(tmp_path):
    """A zero-arg callable defers even the host copy to the writer
    thread (the serving snapshot path for immutable device leaves)."""
    saver = ckpt.AsyncCheckpointer(str(tmp_path))
    payload = {"a": jnp.arange(6, dtype=jnp.float32), "b": np.arange(3)}
    saver.save(1, lambda: payload)
    saver.wait()
    restored = ckpt.restore_checkpoint(
        ckpt.latest_checkpoint(str(tmp_path)),
        {"a": np.zeros(6, np.float32), "b": np.zeros(3, np.int64)})
    np.testing.assert_array_equal(np.asarray(restored["a"]),
                                  np.arange(6, dtype=np.float32))
    np.testing.assert_array_equal(np.asarray(restored["b"]), np.arange(3))


@pytest.mark.parametrize("dtype,values", [
    ("bfloat16", [1.5, -2.0, 0.0, 3.25]),
    ("float16", [1.5, -2.0, 0.0, 3.25]),
    ("bool", [True, False, True, True]),
    ("int32", [1, -7, 0, 2**31 - 1]),
    ("float64", [1.0 / 3.0, -1e300, 0.0, 2.5]),
])
def test_checkpoint_dtype_roundtrip(tmp_path, dtype, values):
    """Non-float64 leaves must survive the manifest dtype path — bf16 in
    particular comes back from ``np.load`` as raw void bytes and is only
    recovered through the manifest's dtype record."""
    if dtype == "bfloat16":
        import ml_dtypes
        arr = np.asarray(values, ml_dtypes.bfloat16)
    else:
        arr = np.asarray(values, np.dtype(dtype))
    path = ckpt.save_checkpoint(str(tmp_path), 1, {"leaf": arr})
    _, arrays, names = ckpt.load_checkpoint_arrays(path)
    assert names == ["['leaf']"]
    assert arrays[0].dtype == arr.dtype
    np.testing.assert_array_equal(arrays[0], arr)
    restored = ckpt.restore_checkpoint(path, {"leaf": np.zeros_like(arr)})
    if dtype == "float64" and not jax.config.jax_enable_x64:
        # the template path goes through device_put, which truncates
        # float64 to float32 with x64 disabled — exact f64 scalars must
        # come from load_checkpoint_arrays (what launch.train does for
        # the model-selection best); pin the behavior so a silent change
        # doesn't invalidate that workaround
        np.testing.assert_array_equal(np.asarray(restored["leaf"]),
                                      arr.astype(np.float32))
    else:
        np.testing.assert_array_equal(np.asarray(restored["leaf"]), arr)


def test_straggler_detector_injected_clock():
    """With an injected clock the heartbeat timeout is fully
    deterministic — no ``time.time()`` in the loop (the serving layer
    injects its virtual clock this way)."""
    now = [0.0]
    det = StragglerDetector(n_hosts=2, dead_after_s=5.0,
                            clock=lambda: now[0])
    det.record(HeartbeatRecord(0, 0, 1.0, timestamp=0.0))
    det.record(HeartbeatRecord(1, 0, 1.0, timestamp=0.0))
    assert det.dead_hosts() == []
    now[0] = 4.0
    assert det.dead_hosts() == []
    now[0] = 6.0  # both silent past the deadline on the virtual clock
    assert det.dead_hosts() == [0, 1]
    det.record(HeartbeatRecord(1, 1, 1.0, timestamp=6.0))
    assert det.dead_hosts() == [0]


@pytest.mark.slow
def test_flexai_trainer_snapshot_resume_bit_exact(tmp_path):
    """Kill the FlexAI training run after 2 of 4 episodes and resume from
    the full-state snapshot: env steps, model-selection best and final
    weights must all match the uninterrupted 4-episode run bit-exactly
    (replay ring, PRNG key and counters ride in the snapshot)."""
    import re
    import subprocess
    import sys

    base = [sys.executable, "-m", "repro.launch.train", "--flexai",
            "--routes", "2", "--rate-scale", "0.005", "--eval-every", "2",
            "--seed", "0"]
    env = dict(os.environ)
    env["PYTHONPATH"] = "src" + os.pathsep + env.get("PYTHONPATH", "")

    def run(args):
        r = subprocess.run(base + args, env=env, capture_output=True,
                           text=True, timeout=420)
        assert r.returncode == 0, f"train failed:\n{r.stdout}\n{r.stderr}"
        m = re.search(r"trained (\d+) env steps .* best_eval_stm=(\S+)",
                      r.stdout)
        assert m, r.stdout
        return int(m.group(1)), m.group(2)

    w_full = str(tmp_path / "full.npz")
    steps_full, best_full = run(["--episodes", "4", "--weights", w_full])

    snap = str(tmp_path / "snaps")
    run(["--episodes", "2", "--snapshot-dir", snap])
    w_res = str(tmp_path / "resumed.npz")
    steps_res, best_res = run(["--episodes", "2", "--snapshot-dir", snap,
                               "--resume", "--weights", w_res])

    assert best_res == best_full
    with np.load(w_full) as a, np.load(w_res) as b:
        assert sorted(a.files) == sorted(b.files)
        for k in a.files:
            np.testing.assert_array_equal(a[k], b[k])


def _flexai_route(n: int, seed: int):
    from repro.core.tasks import TaskArrays
    rng = np.random.default_rng(seed)
    return TaskArrays(
        kind=rng.integers(0, 3, n).astype(np.int32),
        arrival=np.sort(rng.uniform(0, 0.01 * n, n)).astype(np.float32),
        safety=np.full(n, 0.05, np.float32),
        group=np.zeros(n, np.int32), valid=np.ones(n, bool))


def test_flexai_on_episode_ends_the_loop_only_when_it_returns_true():
    """The launcher's trainer, driven through ``ScanFlexAI.train``: a hook
    that returns None sees every episode and leaves the episodes, the
    eval cadence, the weights and a snapshot taken in it as they are
    without it; one that returns True after episode k leaves what a run
    of k + 1 episodes leaves."""
    from repro.launch.train import _trainer_snapshot, build_flexai_trainer
    routes = [_flexai_route(300, s) for s in (1, 2, 3)]
    held_out = _flexai_route(300, 9)

    def train(episodes, hook=None, start=0, trainer=None):
        tr = trainer or build_flexai_trainer(seed=7, rate_scale=0.05)
        hist = tr.train(routes, episodes, eval_queue=held_out, eval_every=2,
                        on_episode=hook, start_episode=start)
        return tr, hist

    def same_weights(a, b):
        for x, y in zip(jax.device_get(a.eval_params()),
                        jax.device_get(b.eval_params())):
            np.testing.assert_array_equal(x, y)

    plain, h_plain = train(5)
    assert [("eval_stm" in h) for h in h_plain] == [False, True, False,
                                                    True, False]
    seen, snaps = [], {}

    def observe(ep, tr):
        seen.append(ep)
        if ep == 2:
            snaps[ep + 1] = jax.device_get(_trainer_snapshot(tr, ep + 1))

    hooked, h_hooked = train(5, observe)
    assert seen == [0, 1, 2, 3, 4]
    assert h_hooked == h_plain and hooked.losses == plain.losses
    same_weights(hooked, plain)

    stopped, h_stopped = train(5, lambda ep, tr: ep == 2)
    short, h_short = train(3)
    assert len(h_stopped) == 3 and h_stopped == h_short
    same_weights(stopped, short)

    snap = snaps[3]
    resumed = build_flexai_trainer(seed=7, rate_scale=0.05)
    resumed.ts = jax.device_put(snap["ts"])
    if bool(snap["has_best"]):
        resumed._best_stm = float(snap["best_stm"])
        resumed._best_params = jax.device_put(snap["best_p"])
    _, h_resumed = train(5, start=3, trainer=resumed)
    assert h_resumed == h_plain[3:]
    same_weights(resumed, plain)
