"""Every name in BENCHMARK.json resolves to its files, and a new
configuration, mix or metric is found without editing a file."""
import json
import shutil

import benchtest
import pytest

from benchlib.registry import Registry
from benchlib.traffic import Mix


def test_every_name_resolves():
    reg = Registry()
    spec = reg.spec
    for c in spec["configs"]:
        cfg = reg.config(c["name"])
        assert reg.config_path(c["name"]) == reg.root / c["file"]
        assert hasattr(reg.driver(cfg["driver"]), "run")
        assert hasattr(reg.reference(cfg["reference"]), "replay")
    for w in spec["workloads"]:
        assert Mix.load(reg.traffic_path(w["traffic"])).windows()
        assert w["config"] in {c["name"] for c in spec["configs"]}
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert callable(reg.metric_reader(m["name"]).read)
        for w in m.get("workloads", []):
            reg.workload(w)
    kinds = reg.peaks()["devices"]
    assert kinds["TPU v5 lite"]["bf16_flops"] == 197e12


def test_metrics_of_a_cell_follow_their_workloads_key():
    reg = Registry()
    e2e = {m["name"] for m in reg.metrics_of("vehicle_ub_100ms", False)}
    assert e2e == {"request_p50_ms", "setup_s"}
    tail = {m["name"] for m in reg.metrics_of("vehicle_ub_100ms", True)}
    assert "request_p95_ms.vehicle" in tail
    layer = {m["name"] for m in reg.metrics_of("fleet_backlog_1s", True)}
    assert "pad_share.fleet" in layer and "idle_share.vehicle" not in layer
    with pytest.raises(KeyError):
        reg.workload("no_such_cell")


def test_new_files_are_found_without_editing_existing_ones(tmp_path):
    reg = benchtest.small_registry(tmp_path)
    before = {p: p.read_bytes() for p in reg.bench.rglob("*")
              if p.is_file()}
    bench = reg.bench
    cfg = json.loads((bench / "configs" / "hmai_fifo_serve.json")
                     .read_text())
    cfg["engine"]["slots"] = 8
    (bench / "configs" / "hmai_fifo_serve_8.json").write_text(
        json.dumps(cfg))
    shutil.copy(bench / "traffic" / "small.json",
                bench / "traffic" / "small_b.json")
    (bench / "metrics" / "dispatches_per_s.new.py").write_text(
        "def read(ctx):\n    return ctx['dispatches'] / ctx['window_s']\n")
    spec = json.loads((tmp_path / "BENCHMARK.json").read_text())
    spec["workloads"].append({"name": "small_b", "config":
                              "hmai_fifo_serve_8", "traffic": "small_b",
                              "chips": 1, "why": "test"})
    spec["per_layer"].append({"name": "dispatches_per_s.new", "unit": "1/s",
                              "better": "higher", "source": "host_clock",
                              "layer": "x", "moves": "decisions_per_s",
                              "workloads": ["small_b"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    reg2 = Registry(root=tmp_path, bench=bench)
    assert reg2.config(reg2.workload("small_b")["config"])["engine"][
        "slots"] == 8
    assert Mix.load(reg2.traffic_path("small_b")).windows()
    reader = reg2.metric_reader("dispatches_per_s.new")
    assert reader.read({"dispatches": 10, "window_s": 2.0}) == 5.0
    assert "dispatches_per_s.new" in {
        m["name"] for m in reg2.metrics_of("small_b", True)}
    for p, data in before.items():
        assert p.read_bytes() == data
