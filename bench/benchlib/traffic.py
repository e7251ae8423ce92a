"""The one traffic generator: camera-task streams of driving routes, cut
into placement requests.

A traffic mix is a data file (``bench/traffic/<name>.json``) of the
parameters below; this module is the only code that reads one.  The
route arithmetic is a copy of the paper's driving environment (arXiv
2104.10415, §2.2 and §8.1): a route is a timeline of go-straight, turn
and reverse segments bounded by the Table-13 parameters, every camera
group of Table 4 fires at its (area, scenario) rate calibrated to the
Table-5 aggregates, every frame is one detection task (YOLO and SSD
alternating per camera) and, except rear cameras outside reversing, one
GOTURN tracking task.  Its safety time is the RSS budget of §6.1.  The
arithmetic and the order of random draws follow the environment the
program ships, so a route here holds the same tasks as the program's own
route of the same seed.

Keys of a mix file:

* ``vehicles``: list of ``{"area": "UB"|"UHW"|"HW", "route_seed": int}``;
* ``route``: ``route_km``, ``velocity_kmh``, ``max_times_turn``,
  ``max_times_reverse``, ``max_duration_turn``, ``max_duration_reverse``;
* ``rate_scale``: camera-rate factor (1.0 = full Table-5 rates);
* ``window_s``: driving seconds per request;
* ``arrivals``: ``"closed_backlog"`` (every window queued at the start,
  each completed one replaced by the next of a cyclic pass) or
  ``"open_loop"`` (each request released at its wall due time, the end
  of its window of driving, counted from the run's start; the windows of
  one index, one per vehicle, fall due together: :func:`release_plan`);
* ``close_waves`` (closed backlog, default 1): the window closes at the
  first wave boundary at or after its length at which the waves served
  in it number a multiple of this, so that it holds whole periods of a
  backlog whose waves alternate in size.
"""
from __future__ import annotations

import dataclasses
import json
import math
import pathlib

import numpy as np

# task kinds and camera groups, in the program's integer encoding
YOLO, SSD, GOTURN = 0, 1, 2
GROUPS = (("FC", 11), ("FLSC", 4), ("RLSC", 4), ("FRSC", 4), ("RRSC", 4),
          ("RC", 3))                                     # Table 4
GROUP_INDEX = {g: i for i, (g, _) in enumerate(GROUPS)}

# per-camera Hz in the urban area by scenario (Table-5 aggregates:
# GS 870/840, TL 950/920, RE 740/740 DET/TRA FPS)
URBAN_HZ = {
    "GS": {"FC": 40.0, "FLSC": 25.0, "RLSC": 25.0, "FRSC": 25.0,
           "RRSC": 25.0, "RC": 10.0},
    "TL": {"FC": 40.0, "FLSC": 30.0, "RLSC": 30.0, "FRSC": 30.0,
           "RRSC": 30.0, "RC": 10.0},
    "RE": {"FC": 20.0, "FLSC": 25.0, "RLSC": 25.0, "FRSC": 25.0,
           "RRSC": 25.0, "RC": 40.0},
}
AREA_SCALE = {"UB": 1.0, "UHW": 1.15, "HW": 1.3}       # Fig-1 trend

# RSS safety time (§6.1, Eq. 1)
A_MAX_ACCEL, A_BRAKE = 8.382, 6.2
SPEED_LIMIT_KMH = {"UB": 60.0, "UHW": 80.0, "HW": 120.0}
TURN_SPEED_KMH = 50.0
CAMERA_MAX_DISTANCE = {"FC": 250.0, "RC": 100.0, "FLSC": 80.0,
                       "RLSC": 80.0, "FRSC": 80.0, "RRSC": 80.0}


def safety_time(group: str, area: str, scenario: str) -> float:
    """Largest processing time rho with RSS distance d(rho) <= the
    camera's range, for an obstacle closing at the vehicle's own speed."""
    v_kmh = SPEED_LIMIT_KMH[area]
    if scenario == "TL":
        v_kmh = min(v_kmh, TURN_SPEED_KMH)
    if scenario == "RE":
        v_kmh = min(v_kmh, 10.0)
    v = v_kmh / 3.6
    a, b = A_MAX_ACCEL, A_BRAKE
    qa = a + a * a / (2 * b) + a * a / (2 * b)
    qb = v + v + a * v / b + a * v / b
    qc = v * v / (2 * b) + v * v / (2 * b) - CAMERA_MAX_DISTANCE[group]
    if qc >= 0:
        return 0.0
    return (-qb + math.sqrt(qb * qb - 4 * qa * qc)) / (2 * qa)


@dataclasses.dataclass(frozen=True)
class RouteParams:
    route_km: float = 0.03
    velocity_kmh: float = 60.0
    max_times_turn: int = 10
    max_times_reverse: int = 10
    max_duration_turn: float = 10.0
    max_duration_reverse: float = 20.0


def _segments(rng, area: str, p: RouteParams) -> list:
    """Scenario timeline ``[(scenario, start, duration)]``: the draws of
    the program's environment, in its order."""
    route_s = p.route_km / p.velocity_kmh * 3600.0
    n_turn = int(rng.integers(0, p.max_times_turn + 1))
    n_rev = 0 if area == "HW" else int(rng.integers(0, p.max_times_reverse
                                                    + 1))
    events = [("TL", rng.uniform(1.0, p.max_duration_turn))
              for _ in range(n_turn)]
    events += [("RE", rng.uniform(1.0, p.max_duration_reverse))
               for _ in range(n_rev)]
    rng.shuffle(events)
    free = max(route_s - sum(d for _, d in events), 0.0)
    gaps = (rng.dirichlet(np.ones(len(events) + 1)) * free if events
            else np.array([free]))
    segs, t = [], 0.0
    for i, (sc, d) in enumerate(events):
        if gaps[i] > 0:
            segs.append(("GS", t, gaps[i]))
            t += gaps[i]
        segs.append((sc, t, d))
        t += d
    if gaps[-1] > 0:
        segs.append(("GS", t, gaps[-1]))
    return segs


def build_route(area: str, seed: int, params: RouteParams,
                rate_scale: float = 1.0) -> dict:
    """One vehicle's whole route as arrays sorted by arrival: ``kind``,
    ``group`` (int32), ``arrival`` (float64 s), ``safety`` (float32 s)."""
    rng = np.random.default_rng(seed)
    segs = _segments(rng, area, params)
    parts = []           # per camera and segment, in the program's order
    det_count: dict = {}
    for sc, start, dur in segs:
        end = start + dur
        for group, count in GROUPS:
            hz = URBAN_HZ[sc][group] * AREA_SCALE[area] * rate_scale
            if hz <= 0:
                continue
            period = 1.0 / hz
            st = safety_time(group, area, sc)
            tracked = group != "RC" or sc == "RE"
            for cam in range(count):
                t0 = start + rng.uniform(0, period)
                # t += period, as the environment does it: a running sum
                n = max(int((end - t0) / period) + 2, 1)
                t = np.cumsum(np.concatenate([[t0], np.full(n, period)]))
                t = t[: int(np.searchsorted(t, end, side="left"))]
                if t.size == 0:
                    continue
                k0 = det_count.get((group, cam), 0)
                det_count[(group, cam)] = k0 + t.size
                det = np.where((k0 + np.arange(t.size)) % 2 == 0, YOLO, SSD)
                if tracked:
                    kind = np.stack([det, np.full(t.size, GOTURN)],
                                    1).reshape(-1)
                    arr = np.repeat(t, 2)
                else:
                    kind, arr = det, t
                parts.append((kind, arr, GROUP_INDEX[group], st))
    kind = np.concatenate([p[0] for p in parts]).astype(np.int32)
    arrival = np.concatenate([p[1] for p in parts])
    group = np.concatenate([np.full(p[0].size, p[2], np.int32)
                            for p in parts])
    safety = np.concatenate([np.full(p[0].size, p[3]) for p in parts])
    order = np.argsort(arrival, kind="stable")
    return {"kind": kind[order], "arrival": arrival[order],
            "group": group[order], "safety": safety[order].astype(np.float32)}


@dataclasses.dataclass(frozen=True)
class Window:
    """One request: ``window_s`` seconds of one vehicle's driving, its
    arrival times rebased to the window's start."""
    vehicle: int
    index: int            # window number along the route
    kind: np.ndarray      # int32 [n]
    arrival: np.ndarray   # float32 [n], seconds since the window start
    safety: np.ndarray    # float32 [n]
    group: np.ndarray     # int32 [n]

    @property
    def n(self) -> int:
        return int(self.kind.shape[0])


def cut_windows(route: dict, window_s: float, vehicle: int = 0) -> list:
    """Cut a route into consecutive windows of ``window_s`` seconds.  The
    windows partition the route's tasks: every task lies in exactly one,
    in route order.  Windows that hold no task are left out."""
    arr = route["arrival"]
    n_win = int(math.floor(arr[-1] / window_s)) + 1 if arr.size else 0
    edges = np.searchsorted(arr, np.arange(n_win + 1) * window_s,
                            side="left")
    edges[-1] = arr.size
    out = []
    for k in range(n_win):
        lo, hi = int(edges[k]), int(edges[k + 1])
        if hi == lo:
            continue
        out.append(Window(
            vehicle=vehicle, index=k, kind=route["kind"][lo:hi],
            arrival=(arr[lo:hi] - k * window_s).astype(np.float32),
            safety=route["safety"][lo:hi], group=route["group"][lo:hi]))
    return out


@dataclasses.dataclass(frozen=True)
class Mix:
    name: str
    arrivals: str
    window_s: float
    rate_scale: float
    route: RouteParams
    vehicles: tuple
    close_waves: int = 1

    @classmethod
    def load(cls, path) -> "Mix":
        path = pathlib.Path(path)
        spec = json.loads(path.read_text())
        return cls.from_dict(path.stem, spec)

    @classmethod
    def from_dict(cls, name: str, spec: dict) -> "Mix":
        if spec["arrivals"] not in ("closed_backlog", "open_loop"):
            raise ValueError(f"traffic {name}: unknown arrivals "
                             f"{spec['arrivals']!r}")
        return cls(name=name, arrivals=spec["arrivals"],
                   window_s=float(spec["window_s"]),
                   rate_scale=float(spec.get("rate_scale", 1.0)),
                   route=RouteParams(**spec.get("route", {})),
                   vehicles=tuple((v["area"], int(v["route_seed"]))
                                  for v in spec["vehicles"]),
                   close_waves=int(spec.get("close_waves", 1)))

    def windows(self) -> list:
        """Every window of every vehicle, in (driving time, vehicle)
        order — the order a recorded fleet drive is replayed in."""
        out = []
        for i, (area, seed) in enumerate(self.vehicles):
            route = build_route(area, seed, self.route, self.rate_scale)
            out += cut_windows(route, self.window_s, vehicle=i)
        out.sort(key=lambda w: (w.index, w.vehicle))
        return out


def release_plan(windows: list, first: int, seconds: float,
                 window_s: float) -> list:
    """Due times of an open loop: for requests ``first, first + 1, ...``
    over ``windows`` (in (driving time, vehicle) order, taken cyclically),
    the seconds from the start of request ``first``'s window of driving
    to the end of each request's own window, for every request due within
    ``seconds``.  A cyclic pass adds the route's length in windows to the
    driving time, and a window that holds no task is never due."""
    n_index = max(w.index for w in windows) + 1
    L = len(windows)

    def drive(k):
        return (k // L) * n_index + windows[k % L].index

    start = drive(first)
    limit = int(round(seconds / window_s))
    out = []
    k = first
    while drive(k) + 1 - start <= limit:
        out.append((drive(k) + 1 - start) * window_s)
        k += 1
    return out
