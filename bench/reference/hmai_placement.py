"""Plain NumPy reference of FlexAI greedy placement on the HMAI platform.

Imports nothing of the program.  Given a configuration file's tables
(Table-8 rates and powers, the Task-Info features) and the Q-net weights
the benchmark made from its seed, it replays served requests with their
served placements (teacher forcing) and returns, per request and task:

* the reference Q values in float64 at the served platform state, so the
  gap by which a served placement's Q lies below the reference's best can
  be read;
* the deadline verdicts of the platform's clock arithmetic in float32,
  operation for operation as the engine's step states it (``start =
  max(arrival, avail[a])``, ``finish = start + exec``, ``response =
  finish - arrival``, ``met = response <= safety``);
* the same readings from the control: the reference computed one step
  below the stated precision — the Q-net's float32 matmuls at "high"
  (three bfloat16 passes) instead of "highest", its other float32 work
  (the observation and the activations) and the clock in bfloat16.

The platform semantics are those of the paper's HW-Info update (§7.2):
per accelerator the next-free time, busy time, energy, latest finish,
summed Matching Score, running utilisation balance and task count.

Requests are replayed in lockstep, one task position at a time over all
of them, so the Python loop runs once per position and not per task.
"""
from __future__ import annotations

import numpy as np

GOTURN = 2


def tables(config: dict) -> dict:
    """Float64 tables of a configuration file: per accelerator the
    Table-8 execution time and energy of each task kind, and the
    Task-Info features (Amount / 30e9, LayerNum / 100)."""
    plat = config["platform"]
    kinds = plat["kinds"]
    accels = []
    for name, count in plat["accelerators"]:
        accels += [name] * count
    scale = float(plat.get("capacity_scale", 1.0))
    exec_time = np.asarray([[1.0 / (plat["fps"][a][k] * scale)
                             for k in kinds] for a in accels])
    energy = np.asarray([[plat["power_w"][a] / (plat["fps"][a][k] * scale)
                          for k in kinds] for a in accels])
    ti = config["task_info"]
    feat = np.asarray([[ti[k]["macs"] / 30e9, ti[k]["layers"] / 100.0]
                       for k in kinds])
    return {"exec": exec_time, "energy": energy, "feat": feat,
            "n": len(accels),
            "backlog_scale": float(config["engine"]["backlog_scale"])}


# -- bfloat16 rounding, for the control ------------------------------------

def to_bf16(x) -> np.ndarray:
    """Round float32 values to the nearest bfloat16 (ties to even), kept
    in float32."""
    b = np.asarray(x, np.float32).view(np.uint32)
    b = (b + np.uint32(0x7FFF) + ((b >> np.uint32(16)) & np.uint32(1))) \
        & np.uint32(0xFFFF0000)
    return b.view(np.float32)


def matmul_high(a, b) -> np.ndarray:
    """A float32 matmul at "high" precision: three bfloat16 passes
    (hi*hi + hi*lo + lo*hi) accumulated in float32.  Every product of two
    bfloat16 values is exact in float32."""
    a = np.asarray(a, np.float32)
    b = np.asarray(b, np.float32)
    a_hi, b_hi = to_bf16(a), to_bf16(b)
    a_lo, b_lo = to_bf16(a - a_hi), to_bf16(b - b_hi)
    return (a_hi @ b_hi + a_hi @ b_lo) + a_lo @ b_hi


def qnet(params, x):
    w1, b1, w2, b2, w3, b3 = params
    h = np.maximum(x @ w1 + b1, 0)
    h = np.maximum(h @ w2 + b2, 0)
    return h @ w3 + b3


def qnet_control(params, x):
    """The Q-net one step below its stated precision: bfloat16
    observation and activations, float32 matmuls at "high"."""
    w1, b1, w2, b2, w3, b3 = [np.asarray(w, np.float32) for w in params]
    h = to_bf16(x)
    h = to_bf16(np.maximum(matmul_high(h, w1) + b1, 0))
    h = to_bf16(np.maximum(matmul_high(h, w2) + b2, 0))
    return to_bf16(matmul_high(h, w3) + b3)


def replay(tab: dict, params, kind, arrival, safety, n_tasks, actions,
           control: bool = False) -> dict:
    """Replay ``R`` requests in lockstep.

    ``kind``, ``arrival``, ``safety``, ``actions``: ``[R, T]`` arrays
    (positions at or past ``n_tasks[r]`` are ignored); ``params``: the six
    Q-net arrays.  Returns ``[R, T]`` arrays: ``gap`` (reference best Q
    minus the Q of the served placement), ``met32`` (float32 verdicts of
    the served placements), and with ``control`` also ``ctrl_gap`` (the
    reference gap of the placement the control puts first) and
    ``ctrl_met`` (bfloat16-clock verdicts)."""
    p64 = [np.asarray(w, np.float64) for w in params]
    kind = np.asarray(kind, np.int64)
    arr32 = np.asarray(arrival, np.float32)
    arr = arr32.astype(np.float64)
    safe32 = np.asarray(safety, np.float32)
    acts = np.asarray(actions, np.int64)
    n_tasks = np.asarray(n_tasks, np.int64)
    R, T = kind.shape
    n = tab["n"]
    ex, en, feat = tab["exec"], tab["energy"], tab["feat"]
    ex32 = ex.astype(np.float32)

    avail = np.zeros((R, n))
    busy = np.zeros((R, n))
    E = np.zeros((R, n))
    Tm = np.zeros((R, n))
    MS = np.zeros((R, n))
    RB = np.zeros((R, n))
    cnt = np.zeros((R, n))
    e_scale = np.full(R, 1e-9)
    avail32 = np.zeros((R, n), np.float32)
    avail16 = np.zeros((R, n), np.float32)

    gap = np.zeros((R, T))
    met32 = np.zeros((R, T), bool)
    ctrl_gap = np.zeros((R, T))
    ctrl_met = np.zeros((R, T), bool)
    rows = np.arange(R)
    for t in range(T):
        live = t < n_tasks
        if not live.any():
            break
        k, a, at = kind[:, t], acts[:, t], arr[:, t]
        # observation: Task-Info, then per accelerator (energy, backlog,
        # balance, mean MS, execution time of this task's kind)
        backlog = np.log1p(np.maximum(avail - at[:, None], 0.0)
                           / tab["backlog_scale"])
        hw = np.stack([E / np.maximum(e_scale, 1e-9)[:, None], backlog, RB,
                       MS / np.maximum(cnt, 1.0), ex[:, k].T], axis=2)
        sv = np.concatenate([feat[k], safe32[:, t, None].astype(np.float64),
                             hw.reshape(R, -1)], axis=1)
        q = qnet(p64, sv)
        best = q.max(axis=1)
        gap[:, t] = np.where(live, best - q[rows, a], 0.0)
        if control:
            qc = qnet_control(params, sv)
            ctrl_gap[:, t] = np.where(
                live, best - q[rows, np.argmax(qc, axis=1)], 0.0)

        # float32 clock of the served placement
        e32 = ex32[a, k]
        start32 = np.maximum(arr32[:, t], avail32[rows, a])
        fin32 = (start32 + e32).astype(np.float32)
        resp32 = (fin32 - arr32[:, t]).astype(np.float32)
        met32[:, t] = resp32 <= safe32[:, t]
        avail32[rows[live], a[live]] = fin32[live]
        if control:
            s16 = np.maximum(to_bf16(arr32[:, t]), avail16[rows, a])
            f16 = to_bf16(s16 + to_bf16(e32))
            r16 = to_bf16(f16 - to_bf16(arr32[:, t]))
            ctrl_met[:, t] = r16 <= to_bf16(safe32[:, t])
            avail16[rows[live], a[live]] = f16[live]

        # float64 HW-Info update (§7.2)
        et = ex[a, k]
        start = np.maximum(at, avail[rows, a])
        finish = start + et
        response = finish - at
        safe = safe32[:, t].astype(np.float64)
        ok = response <= safe
        ms = np.where(k == GOTURN, np.where(ok, 1.0, -1.0),
                      np.where(ok & (safe > 0),
                               response / np.maximum(safe, 1e-12), -1.0))
        r, c = rows[live], a[live]
        avail[r, c] = finish[live]
        busy[r, c] += et[live]
        E[r, c] += en[a, k][live]
        Tm[r, c] = np.maximum(Tm[r, c], finish[live])
        MS[r, c] += ms[live]
        cnt[r, c] += 1.0
        util = busy[r, c] / np.maximum(finish[live], 1e-9)
        RB[r, c] = (util + RB[r, c] * (cnt[r, c] - 1.0)) / cnt[r, c]
        e_scale = np.where(live, np.maximum(e_scale, E.sum(axis=1)),
                           e_scale)
    out = {"gap": gap, "met32": met32}
    if control:
        out["ctrl_gap"] = ctrl_gap
        out["ctrl_met"] = ctrl_met
    return out
