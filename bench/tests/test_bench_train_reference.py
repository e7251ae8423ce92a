"""The training cell: its files resolve, the program's fused trainer agrees
with the plain reference (``bench/reference/flexai_train.py``) with the
TD kernel (interpreted) and with the XLA update, the control fails, and
three faults planted in the trainer each make the run not correct.

CPU, full Q-net widths, a short full-rate UB route; ``min_replay`` and
``target_sync_every`` lowered so that TD updates and TargNet syncs fall
inside the checked episode."""
import dataclasses
import io
import json
import shutil

import benchtest
import jax
import numpy as np
import pytest

import run as bench_run
from benchlib.registry import Registry
from benchlib.result import validate
from benchlib.traffic import Mix, RouteParams, build_route

CELL = "train_ub_32k"
N_TASKS, PREFIX = 400, 32
SYNC = 20


def _registry_parts():
    reg = Registry()
    cfg = reg.config(reg.workload(CELL)["config"])
    return reg, cfg, reg.driver(cfg["driver"]), reg.reference(
        cfg["reference"])


def test_the_cells_files_resolve():
    reg, cfg, drv, ref = _registry_parts()
    w = reg.workload(CELL)
    assert w["chips"] == 1
    assert reg.config_path(w["config"]) == reg.root / next(
        c["file"] for c in reg.spec["configs"] if c["name"] == w["config"])
    assert callable(drv.run) and callable(ref.replay)
    mix = Mix.load(reg.traffic_path(w["traffic"]))
    extra = json.loads(reg.traffic_path(w["traffic"]).read_text())
    assert extra["episode_tasks"] == 32768 and extra["eval_every"] == 5
    assert extra["eval_route_seed"] == 50
    assert [s for _, s in mix.vehicles] == list(range(8))
    assert {m["name"] for m in reg.metrics_of(CELL, False)} == {
        "decisions_per_s", "setup_s"}
    layer = {m["name"] for m in reg.metrics_of(CELL, True)}
    assert layer == {"host_ms_per_episode.train", "device_us_per_step.train",
                     "td_update_share.train", "td_update_roofline.train",
                     "train_mfu"}
    for name in layer:
        assert callable(reg.metric_reader(name).read)
        assert reg.metric_reader(name).read({"config": cfg}) is None
    for area, seed in mix.vehicles[:1]:
        route = build_route(area, seed, mix.route, mix.rate_scale)
        assert route["kind"].size >= extra["episode_tasks"]


def _config(cfg: dict, trainer) -> dict:
    """The cell's configuration with the test trainer's settings."""
    block = dict(dataclasses.asdict(trainer), lanes=1, td_kernel=True)
    block.pop("seed")
    return dict(cfg, trainer=block, learning_check_tasks=PREFIX)


def _trainer_cfg(**kw):
    from repro.core.flexai import FlexAIConfig
    base = dict(lr=1e-3, gamma=0.98, min_replay=64, update_every=2,
                eps_decay_steps=2000, target_sync_every=SYNC, seed=3)
    return FlexAIConfig(**dict(base, **kw))


def _routes(drv):
    p = RouteParams(max_times_turn=10, max_times_reverse=10)
    return [drv.episode_tasks(build_route("UB", s, p, 1.0), N_TASKS)
            for s in (0, 1)]


def _program(drv, routes, td_kernel, cfg, sync_every):
    """The fused trainer as the driver holds it: episode 0, then the
    checked episode 1, its learning prefix and its prefix up to its first
    TargNet sync trained again from the same state."""
    from repro.core.flexai import ScanFlexAI
    from repro.core.hmai import HMAIPlatform
    trainer = ScanFlexAI(HMAIPlatform(), cfg, td_kernel=td_kernel)
    trainer.train(routes, 1)
    before = trainer.ts
    trainer.train(routes, 2, start_episode=1)
    outputs = trainer.last_episode
    tasks = routes[1]

    def prefix(n):
        trainer.ts = before
        trainer.train_episode(tasks._replace(
            valid=np.arange(tasks.valid.size) < n))
        return jax.device_get(trainer.ts)

    n_sync = drv.first_sync(outputs[2], int(before.updates), sync_every)
    assert n_sync is not None
    ts = prefix(n_sync)
    synced = all(np.array_equal(t, e) for t, e in zip(ts.targ_p, ts.eval_p))
    return drv.Held(1, jax.device_get(before), outputs,
                    list(prefix(PREFIX).eval_p), synced)


def _judge(program_cfg, td_kernel=True, reference_cfg=None, control=False):
    _, cfg, drv, ref = _registry_parts()
    routes = _routes(drv)
    stated = reference_cfg or program_cfg
    held = _program(drv, routes, td_kernel, program_cfg,
                    stated.target_sync_every)
    config = _config(cfg, stated)
    out = drv.check([held], routes, config, ref, control)
    assert out["update_steps"] > 0 and out["explore_steps"] > 0
    judged = bench_run.judge(dict(out["checks"], unanswered=0),
                             config["checks"])
    if control:
        return judged, bench_run.judge(dict(out["control"], unanswered=0),
                                       config["checks"])
    return judged


@pytest.mark.parametrize("td_kernel", [True, False],
                         ids=["kernel_interpret", "xla_update"])
def test_program_agrees_with_the_reference(td_kernel):
    correct, checks = _judge(_trainer_cfg(), td_kernel=td_kernel)
    assert correct, checks
    assert checks["random_action_mismatch"]["value"] == 0
    assert checks["verdict_mismatch"]["value"] == 0


def test_the_control_fails_where_the_program_passes():
    (correct, checks), (ctrl_ok, ctrl) = _judge(_trainer_cfg(),
                                                control=True)
    assert correct, checks
    assert not ctrl_ok, ctrl


def _skipped_sync():
    """The trainer never copies EvalNet into TargNet."""
    return {"program_cfg": _trainer_cfg(target_sync_every=10**9),
            "reference_cfg": _trainer_cfg()}


def _wrong_replay_index(monkeypatch):
    """The TD batch is read one ring row past the sampled index."""
    from repro.core.flexai import engine
    real = engine.device_replay_sample

    def shifted(buf, key, batch_size):
        batch = real(buf, key, batch_size)
        idx = jax.random.randint(key, (batch_size,), 0,
                                 jax.numpy.maximum(buf.size, 1)) + 1
        return {k: getattr(buf, k)[idx] for k in batch}

    monkeypatch.setattr(engine, "device_replay_sample", shifted)
    return {"program_cfg": _trainer_cfg()}


def _flipped_explore_draw(monkeypatch):
    """The epsilon draw is read as 1 - u."""
    from repro.core.flexai import engine

    class Random:
        def __getattr__(self, name):
            return getattr(jax.random, name)

        @staticmethod
        def uniform(*args, **kwargs):
            return 1.0 - jax.random.uniform(*args, **kwargs)

    class Jax:
        random = Random()

        def __getattr__(self, name):
            return getattr(jax, name)

    monkeypatch.setattr(engine, "jax", Jax())
    return {"program_cfg": _trainer_cfg()}


@pytest.mark.parametrize("fault", ["skipped_sync", "wrong_replay_index",
                                   "flipped_explore_draw"])
def test_a_planted_fault_is_not_correct(fault, monkeypatch):
    plant = {"skipped_sync": lambda: _skipped_sync(),
             "wrong_replay_index": lambda: _wrong_replay_index(monkeypatch),
             "flipped_explore_draw":
                 lambda: _flipped_explore_draw(monkeypatch)}[fault]
    correct, checks = _judge(**plant())
    assert not correct, checks
    if fault == "skipped_sync":
        assert checks["sync_mismatch"]["value"] == 1


def test_a_whole_run_of_a_small_training_cell_is_correct(tmp_path):
    """``bench/run.py`` drives the cell's driver end to end on the CPU,
    on episodes of 512 tasks."""
    spec = json.loads((benchtest.ROOT / "BENCHMARK.json").read_text())
    bench = tmp_path / "bench"
    shutil.copytree(benchtest.BENCH, bench,
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    mix = json.loads((bench / "traffic" / f"{CELL}.json").read_text())
    mix.update(episode_tasks=512, vehicles=mix["vehicles"][:2])
    (bench / "traffic" / f"{CELL}.json").write_text(json.dumps(mix))
    spec["workloads"] = [w for w in spec["workloads"] if w["name"] == CELL]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    reg = Registry(root=tmp_path, bench=bench)
    args = bench_run.parse_args(
        ["--workload", CELL, "--seed", str(2**33 + 5), "--seconds", "1",
         "--trace", "0"])
    line = bench_run.execute(args, registry=reg, require_chip=False,
                             use_cache=False, err=io.StringIO())
    assert line["correct"], line["checks"]
    assert line["attempted"] >= 1 and line["failed"] == 0
    assert set(line["metrics"]) == {"decisions_per_s", "setup_s"}
    assert validate(line, False) == []


def test_streamed_trace_reduction_equals_the_harness_reduction():
    """``train_trace.reduce`` reads each line once and agrees with
    ``benchlib.trace.reduce`` on a synthetic two-chip trace, besides
    giving the module and kernel seconds."""
    from benchlib import trace, train_trace
    devices = [
        [["%fusion.1 = f32[4] fusion(...)", 100.0, 200.0],
         ["%dqn_td_update.1 = (f32[1,1]) custom-call(...)", 250.0, 100.0],
         ["copy.3", 600.0, 100.0], ["dqn_td_update.12", 700.0, 50.0],
         ["dqn_td_update_grads.1", 760.0, 10.0], ["copy.3", 950.0, 100.0]],
        [["while.1", 0.0, 400.0], ["dqn_td_update.1", 500.0, 20.0]]]
    planes = [{"name": "/host:CPU", "lines": [{"name": "python", "events": [
        [trace.ANCHOR, 50.0, 1.0]]}]}]
    for i, ops in enumerate(devices):
        planes.append({"name": f"/device:TPU:{i}", "lines": [
            {"name": "XLA Modules", "events": [
                ["jit_train_episode(7)", 50.0, 900.0],
                ["jit_eval_episode(8)", 960.0, 100.0]]},
            {"name": "XLA Ops", "events": ops}]})
    planes.append({"name": "/device:CPU:0", "lines": [
        {"name": "XLA Ops", "events": [["host_op", 0.0, 1e4]]}]})
    spans = [("train", 0.0, 600.0), ("restore", 600.0, 1000.0)]
    want = trace.reduce({"planes": planes}, 0.0, 1000.0, spans)
    # a TPU trace's empty plane is no device of the streamed reduction
    planes.append({"name": "/device:CUSTOM:Megascale Trace", "lines": []})
    got = train_trace.reduce(
        [(p["name"], [(ln["name"], iter(ln["events"])) for ln in p["lines"]])
         for p in planes], 0.0, 1000.0, "dqn_td_update", spans)
    for k in ("busy_s", "window_s", "devices"):
        assert got[k] == pytest.approx(want[k])
    assert dict(got["device_ops"]) == pytest.approx(dict(want["device_ops"]))
    assert dict(got["idle_gaps"]) == pytest.approx(dict(want["idle_gaps"]))
    assert got["kernel_ops"] == ["dqn_td_update.1", "dqn_td_update.12"]
    assert got["kernel_s"] == pytest.approx((100 + 50 + 20) * 1e-9)
    assert got["module_s"] == pytest.approx(
        {"jit_train_episode": 1800e-9, "jit_eval_episode": 80e-9})
    assert got["ops_total"] == 8 and got["ops_outside_window"] == 0
    assert got["planes"]["/device:TPU:0"] == {"XLA Modules": 2, "XLA Ops": 6}
    assert got["devices"] == 2
