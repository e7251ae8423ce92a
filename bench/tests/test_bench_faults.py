"""A whole run, with the chip look skipped and the timed path broken
underneath, comes out as not correct — once for each fault a serving
cell can have.  (One chip: there is no exchange between chips to
leave out.)"""
import benchtest
import jax.numpy as jnp
import pytest


def _state_unchanged(seg):
    def run(params, tasks, state):
        _, recs = seg(params, tasks, state)
        return state, recs
    return run


def _half_batch(seg):
    def run(params, tasks, state):
        lanes = tasks.valid.shape[0]
        keep = jnp.arange(lanes)[:, None] < lanes // 2
        return seg(params, tasks._replace(
            valid=jnp.asarray(tasks.valid) & keep), state)
    return run


def _altered_answer(seg):
    def run(params, tasks, state):
        st, recs = seg(params, tasks, state)
        a = recs.action.at[:, 0].set((recs.action[:, 0] + 1) % 11)
        return st, recs._replace(action=a)
    return run


@pytest.mark.parametrize("fault", [_state_unchanged, _half_batch,
                                   _altered_answer])
def test_a_broken_timed_path_is_not_correct(tmp_path, monkeypatch, fault):
    from repro.serve import qos
    real = qos._segment_fn
    monkeypatch.setattr(qos, "_segment_fn",
                        lambda *a, **k: fault(real(*a, **k)))
    line = benchtest.run_small(tmp_path, seconds=0.5)
    assert not line["correct"], line["checks"]


def test_a_misordered_request_summary_is_not_correct(tmp_path,
                                                   monkeypatch):
    """The engine's summary step hands a request its placements in the
    wrong order, while the segment records stay right."""
    reg = benchtest.small_registry(tmp_path)
    drv = reg.driver(reg.config(reg.workload("small")["config"])["driver"])
    real = drv.engine_class

    def broken():
        base = real()

        class Broken(base):
            def _on_complete(self, req, lane_final, lane_recs):
                req.summary["placements"] = req.summary["placements"][::-1]
                super()._on_complete(req, lane_final, lane_recs)
        return Broken

    monkeypatch.setattr(drv, "engine_class", broken)
    line = benchtest.run_small(tmp_path, seconds=0.5, registry=reg)
    assert not line["correct"], line["checks"]
    assert line["checks"]["drain_errors"]["value"] > 0


def test_the_unbroken_run_is_correct(tmp_path):
    line = benchtest.run_small(tmp_path, seconds=0.5)
    assert line["correct"], line["checks"]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert "decisions_per_s" in line["metrics"]
    assert "setup_s" in line["metrics"]
