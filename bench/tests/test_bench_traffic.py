"""The traffic generator: deterministic, windows partition each route,
and routes equal the program's own environment's."""
import benchtest  # noqa: F401
import numpy as np
import pytest

from benchlib.registry import Registry
from benchlib.traffic import (Mix, RouteParams, build_route, cut_windows,
                              release_plan, safety_time)

SMALL = RouteParams(route_km=0.03, max_times_turn=2, max_times_reverse=1,
                    max_duration_turn=4.0, max_duration_reverse=5.0)


def test_same_seed_same_windows():
    a = Mix.from_dict("a", benchtest.SMALL_MIX).windows()
    b = Mix.from_dict("a", benchtest.SMALL_MIX).windows()
    assert len(a) == len(b) > 4
    for x, y in zip(a, b):
        assert (x.vehicle, x.index) == (y.vehicle, y.index)
        for f in ("kind", "arrival", "safety", "group"):
            assert np.array_equal(getattr(x, f), getattr(y, f))
    other = dict(benchtest.SMALL_MIX, vehicles=[
        {"area": "UB", "route_seed": 99}, {"area": "HW", "route_seed": 12}])
    c = Mix.from_dict("c", other).windows()
    assert [w.n for w in c] != [w.n for w in a]


@pytest.mark.parametrize("area,seed,window_s", [
    ("UB", 0, 1.0), ("UHW", 3, 0.1), ("HW", 5, 0.37)])
def test_windows_partition_the_route(area, seed, window_s):
    route = build_route(area, seed, SMALL, rate_scale=0.2)
    wins = cut_windows(route, window_s)
    assert sum(w.n for w in wins) == route["kind"].size
    assert np.array_equal(np.concatenate([w.kind for w in wins]),
                          route["kind"])
    back = np.concatenate([w.arrival.astype(np.float64) + w.index * window_s
                           for w in wins])
    assert np.allclose(back, route["arrival"], atol=1e-5)
    for w in wins:
        assert w.arrival.min() >= 0 and w.arrival.max() < window_s
    assert [w.index for w in wins] == sorted({w.index for w in wins})


def test_fleet_order_is_driving_time_then_vehicle():
    mix = Mix.load(Registry().traffic_path("fleet_backlog_1s"))
    keys = [(w.index, w.vehicle) for w in mix.windows()]
    assert keys == sorted(keys) and len(set(keys)) == len(keys)


@pytest.mark.parametrize("area,seed,rate", [("UB", 1, 0.05), ("UHW", 2, 0.1),
                                            ("HW", 4, 0.05)])
def test_routes_equal_the_programs_environment(area, seed, rate):
    from repro.core.environment import (Area, EnvironmentParams,
                                        build_task_queue)
    from repro.core.tasks import tasks_to_arrays
    ta = tasks_to_arrays(build_task_queue(EnvironmentParams(
        area=Area(area), route_km=SMALL.route_km, rate_scale=rate,
        seed=seed, max_times_turn=SMALL.max_times_turn,
        max_times_reverse=SMALL.max_times_reverse,
        max_duration_turn=SMALL.max_duration_turn,
        max_duration_reverse=SMALL.max_duration_reverse)))
    r = build_route(area, seed, SMALL, rate_scale=rate)
    assert np.array_equal(r["kind"], ta.kind)
    assert np.array_equal(r["group"], ta.group)
    assert np.array_equal(r["safety"], ta.safety)
    assert np.array_equal(r["arrival"].astype(np.float32), ta.arrival)


def test_safety_times_equal_the_programs():
    from repro.core.criteria import camera_safety_time
    for area in ("UB", "UHW", "HW"):
        for sc in ("GS", "TL", "RE"):
            for g in ("FC", "FLSC", "RC"):
                assert safety_time(g, area, sc) == camera_safety_time(
                    g, area, sc)


def test_unknown_arrivals_are_refused():
    with pytest.raises(ValueError):
        Mix.from_dict("x", dict(benchtest.SMALL_MIX, arrivals="burst"))


def test_an_open_loop_fleet_is_due_at_its_own_driving_time():
    """Two vehicles, open loop: both windows of one index fall due
    together, at the end of that window of driving; a second cyclic pass
    continues the driving clock."""
    spec = dict(benchtest.SMALL_MIX, arrivals="open_loop", window_s=0.5)
    wins = Mix.from_dict("two", spec).windows()
    assert {w.vehicle for w in wins} == {0, 1}
    plan = release_plan(wins, 0, 3.0, 0.5)
    first = wins[0].index
    assert plan == [(w.index - first + 1) * 0.5 for w in wins[:len(plan)]]
    assert plan[0] == plan[1] == 0.5
    assert len(plan) == sum(w.index - first < 6 for w in wins)
    n_index = max(w.index for w in wins) + 1
    span = n_index * 0.5
    wrap = release_plan(wins, len(wins) - 1, span + 1.0, 0.5)
    assert wrap[0] == 0.5
    assert wrap[1] == (n_index + wins[0].index + 1 - wins[-1].index) * 0.5
    later = release_plan(wins, 2, 1.0, 0.5)
    assert later == [(w.index - wins[2].index + 1) * 0.5
                     for w in wins[2:2 + len(later)]]
    assert all(d <= 1.0 for d in later)


def test_an_open_loop_fleet_run_answers_each_vehicles_windows(tmp_path):
    """A whole run of a two-vehicle open loop at 0.5 s windows releases
    both vehicles' windows of every half second of driving."""
    mix = dict(benchtest.SMALL_MIX, arrivals="open_loop", window_s=0.5)
    line = benchtest.run_small(tmp_path, seconds=2.0, mix=mix)
    wins = Mix.from_dict("two", mix).windows()
    assert line["attempted"] == len(release_plan(wins, 0, 2.0, 0.5)) == 8
    assert line["correct"] and line["failed"] == 0, line["checks"]
    assert "request_p50_ms" in line["metrics"]
