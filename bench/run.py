#!/usr/bin/env python3
"""Chip benchmark of the repository's FlexAI placement serving.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

One cell of ``BENCHMARK.json`` per run: its configuration, traffic mix
and metric readers are found by name under ``bench/``
(``benchlib/registry.py``).  The run sets up the system, measures for
``--seconds`` seconds, and with ``--trace 1`` serves a further short
window under the profiler.  Then it replays what the window served
through the configuration's plain reference, and prints one JSON object
as the last line of standard output: ``correct``, ``attempted``,
``failed``, ``metrics`` (end-to-end with ``--trace 0``, per-layer with
``--trace 1``), ``device``, with ``--trace 1`` ``breakdown``, and last
``checks``: each number compared with its limit.  The same numbers are
the last lines of standard error.

It exits non-zero and prints no result when JAX finds no accelerator,
fewer chips than the cell asks for, or a device kind that
``bench/peaks.json`` does not list.  JAX's compilation cache and the
profiler's trace live under ``.bench_cache/`` in the checkout.
"""
from __future__ import annotations

import time

T_PROC0 = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import types  # noqa: E402

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent
TRACE_S = 4.0      # length of the profiled window of a --trace 1 run
for p in (str(ROOT / "src"), str(BENCH)):
    if p not in sys.path:
        sys.path.insert(0, p)


class NoDevice(RuntimeError):
    """The machine lacks what the cell needs; nothing was measured."""


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0,
                    help="judge the control (the reference one step below "
                         "the stated precision, in the program's place) "
                         "instead of the program, whose judgement the "
                         "line keeps under 'program'; not part of a run "
                         "the benchmark's checks make")
    return ap.parse_args(argv)


def enable_cache(path: pathlib.Path) -> None:
    """JAX's persistent compilation cache at a fixed path in the
    checkout, for the benchmark and for any program code that reads
    ``JAX_COMPILATION_CACHE_DIR``."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(path)
    import jax
    jax.config.update("jax_compilation_cache_dir", str(path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def devices(chips: int, peaks: dict, require_chip: bool):
    import jax
    devs = jax.devices()
    plat, kind = devs[0].platform, devs[0].device_kind
    if require_chip:
        if plat == "cpu":
            raise NoDevice("JAX found no accelerator (platform cpu)")
        if len(devs) < chips:
            raise NoDevice(f"the cell needs {chips} chips, JAX found "
                           f"{len(devs)}")
        if kind not in peaks["devices"]:
            raise NoDevice(f"device kind {kind!r} is not in "
                           f"bench/peaks.json")
    return devs[:chips], {"platform": plat, "kind": kind, "count": chips}


def judge(numbers: dict, limits: dict) -> tuple:
    """Each number compared beside its limit, and whether every one
    lies within it."""
    checks = {k: {"value": numbers[k], "limit": lim}
              for k, lim in limits.items()}
    return all(c["value"] <= c["limit"] for c in checks.values()), checks


def memory_peak(devs) -> int:
    peak = 0
    for d in devs:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


def execute(args, registry=None, require_chip: bool = True,
            use_cache: bool = True, err=sys.stderr) -> dict:
    """One run of one cell; returns the result line's object.  Tests
    skip the look for a chip and the persistent compilation cache."""
    from benchlib import result as res
    from benchlib.registry import Registry
    from benchlib.spans import CompileCounter, Timeline
    from benchlib.traffic import Mix

    reg = registry or Registry()
    cell = reg.workload(args.workload)
    peaks = reg.peaks()
    cache = reg.root / ".bench_cache"
    if use_cache:
        enable_cache(cache / "jax")
    devs, device = devices(int(cell["chips"]), peaks, require_chip)
    config = reg.config(cell["config"])
    mix = Mix.load(reg.traffic_path(cell["traffic"]))
    trace_dir = cache / "trace" / args.workload
    if args.trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        trace_dir.mkdir(parents=True)
    run = types.SimpleNamespace(
        config=config, mix=mix, seed=args.seed, seconds=args.seconds,
        trace_s=min(TRACE_S, args.seconds) if args.trace else 0.0,
        trace_dir=trace_dir, timeline=Timeline(),
        compiles=CompileCounter(), reference=reg.reference(
            config["reference"]), control=bool(args.control),
        t_proc0=T_PROC0, read_memory=lambda: memory_peak(devs))
    out = reg.driver(config["driver"]).run(run)
    if args.trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
    gc.collect()

    ctx = dict(out, config=config, chips=len(devs),
               peak=peaks["devices"].get(device["kind"]))
    entries = reg.metrics_of(args.workload, bool(args.trace))
    values = {m["name"]: reg.metric_reader(m["name"]).read(ctx)
              for m in entries}
    units = {m["name"]: m["unit"] for m in entries}
    device["memory_peak_bytes"] = out["memory_peak_bytes"]
    breakdown = None
    if args.trace:
        tr = out["trace"]
        device["busy_s"] = tr["busy_s"]
        device["window_s"] = tr["window_s"]
        breakdown = {"device_ops": tr["device_ops"],
                     "idle_gaps": tr["idle_gaps"]}
    limits = config["checks"]
    correct, checks = judge(dict(out["checks"], unanswered=out["failed"]),
                            limits)
    program = None
    if "control" in out:
        program = {"correct": correct, "checks": checks}
        correct, checks = judge(dict(out["control"],
                                     unanswered=out["failed"]), limits)
    line = res.make_line(correct=correct, attempted=out["attempted"],
                         failed=out["failed"], metrics=values, units=units,
                         device=device, checks=checks, breakdown=breakdown)

    skip = {"checks", "trace", "latencies_ms", "control"}
    for k, v in out.items():
        if k not in skip:
            print(f"run {k} {v}", file=err)
    if args.trace:
        for k in ("busy_s", "window_s", "ops_total", "ops_outside_window",
                  "planes"):
            print(f"trace {k} {out['trace'][k]}", file=err)
    for k, v in values.items():
        print(f"metric {k} {v}", file=err)
    if program is not None:
        for k, c in program["checks"].items():
            print(f"program check {k} {c['value']} limit {c['limit']}",
                  file=err)
        line["program"] = program
        line["checks"] = line.pop("checks")
        print("control in the program's place:", file=err)
    for k, c in checks.items():
        print(f"check {k} {c['value']} limit {c['limit']}", file=err)
    return line


def main(argv=None) -> int:
    args = parse_args(argv)
    try:
        line = execute(args)
    except NoDevice as e:
        print(f"bench: {e}; nothing was measured", file=sys.stderr)
        return 3
    from benchlib.result import dumps, validate
    errs = validate(line, bool(args.trace))
    if errs:
        print(f"bench: malformed result line: {errs}", file=sys.stderr)
        return 4
    sys.stderr.flush()
    print(dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
