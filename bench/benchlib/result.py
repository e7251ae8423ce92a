"""The result line and the statistics it is made from."""
from __future__ import annotations

import json
import math

REQUIRED = ("correct", "attempted", "failed", "metrics", "device")
DEVICE_KEYS = ("platform", "kind", "count", "memory_peak_bytes")
TRACE_DEVICE_KEYS = ("busy_s", "window_s")


def percentile(values, q: float) -> float:
    """The ``q``-th percentile (0-100), linear between closest ranks (as
    ``numpy.percentile`` computes it by default)."""
    xs = sorted(float(v) for v in values)
    if not xs:
        raise ValueError("percentile of no values")
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def make_line(*, correct: bool, attempted: int, failed: int,
              metrics: dict, units: dict, device: dict,
              checks: dict, breakdown: dict | None = None) -> dict:
    """Assemble the last line.  ``metrics`` maps names to values (None:
    left out); ``checks`` maps each number compared to ``{"value",
    "limit"}`` and comes last."""
    line = {
        "correct": bool(correct),
        "attempted": int(attempted),
        "failed": int(failed),
        "metrics": {k: {"value": float(v), "unit": units[k]}
                    for k, v in metrics.items() if v is not None},
        "device": device,
    }
    if breakdown is not None:
        line["breakdown"] = breakdown
    line["checks"] = checks
    return line


def validate(line: dict, trace: bool) -> list:
    """What is wrong with a result line, as a list of messages (empty
    when it is sound)."""
    errs = [f"missing key {k}" for k in REQUIRED if k not in line]
    if errs:
        return errs
    if not isinstance(line["correct"], bool):
        errs.append("correct is not a bool")
    for k in ("attempted", "failed"):
        if not isinstance(line[k], int) or line[k] < 0:
            errs.append(f"{k} is not a count")
    for name, m in line["metrics"].items():
        if set(m) != {"value", "unit"}:
            errs.append(f"metric {name} keys {sorted(m)}")
        elif not isinstance(m["value"], float) or not math.isfinite(
                m["value"]):
            errs.append(f"metric {name} value {m['value']!r}")
    dev = line["device"]
    want = DEVICE_KEYS + (TRACE_DEVICE_KEYS if trace else ())
    errs += [f"device lacks {k}" for k in want if k not in dev]
    if trace and not dev.get("busy_s", 0) > 0:
        errs.append("busy_s is not above 0")
    if "breakdown" in line:
        bd = line["breakdown"]
        for k in ("device_ops", "idle_gaps"):
            if len(bd.get(k, [])) > 10:
                errs.append(f"breakdown {k} has more than 10 entries")
    if list(line)[-1] != "checks":
        errs.append("checks is not the last key")
    for name, c in line.get("checks", {}).items():
        if set(c) != {"value", "limit"}:
            errs.append(f"check {name} keys {sorted(c)}")
    return errs


def dumps(line: dict) -> str:
    return json.dumps(line, separators=(",", ":"))
