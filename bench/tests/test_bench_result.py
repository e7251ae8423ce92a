"""The last line's schema."""
import json

import benchtest  # noqa: F401

from benchlib import result


def _line(**kw):
    base = dict(correct=True, attempted=10, failed=0,
                metrics={"setup_s": 12.5, "absent": None},
                units={"setup_s": "s", "absent": "ms"},
                device={"platform": "tpu", "kind": "TPU v5 lite",
                        "count": 1, "memory_peak_bytes": 1024},
                checks={"q_gap": {"value": 1e-7, "limit": 1e-5}})
    base.update(kw)
    return result.make_line(**base)


def test_a_sound_line_validates_and_checks_come_last():
    line = _line()
    assert result.validate(line, trace=False) == []
    assert list(line) == ["correct", "attempted", "failed", "metrics",
                          "device", "checks"]
    assert "absent" not in line["metrics"]
    assert line["metrics"]["setup_s"] == {"value": 12.5, "unit": "s"}
    back = json.loads(result.dumps(line))
    assert back == line


def test_a_traced_line_needs_busy_and_window():
    line = _line()
    assert any("busy_s" in e for e in result.validate(line, trace=True))
    dev = dict(line["device"], busy_s=0.5, window_s=4.0)
    line = _line(device=dev, breakdown={"device_ops": [["a", 0.1]],
                                        "idle_gaps": [["drain", 3.0]]})
    assert result.validate(line, trace=True) == []
    assert list(line)[-2:] == ["breakdown", "checks"]


def test_faults_in_a_line_are_found():
    line = _line()
    del line["failed"]
    assert result.validate(line, False) == ["missing key failed"]
    line = _line()
    line["metrics"]["x"] = {"value": float("nan"), "unit": "s"}
    line["extra"] = 1
    errs = result.validate(line, False)
    assert any("metric x" in e for e in errs)
    assert any("last key" in e for e in errs)


def test_percentile_is_linear_between_ranks():
    assert result.percentile([1, 2, 3, 4], 50) == 2.5
    assert result.percentile([5], 95) == 5
    assert result.percentile(range(101), 95) == 95
