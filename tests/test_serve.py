"""Serving engine: wave batching, greedy determinism, sampling, and the
deadline-aware (EDF + aging + shedding) admission mode."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models.api import model_api
from repro.models.config import ModelConfig
from repro.serve.engine import (Request, ServeEngine, make_serve_step,
                                sample_token)
from repro.sharding import unbox

KEY = jax.random.PRNGKey(5)

CFG = ModelConfig(name="serve-tiny", family="dense", num_layers=2, d_model=64,
                  num_heads=4, num_kv_heads=2, d_ff=128, vocab_size=64,
                  attention_impl="naive", dtype="float32")


def _engine(slots=2, max_seq=32):
    api = model_api(CFG)
    params = unbox(api.init(KEY))
    return ServeEngine(api, params, slots=slots, max_seq=max_seq)


def test_wave_serving_completes():
    eng = _engine()
    for uid in range(5):
        eng.submit(Request(uid=uid,
                           prompt=np.array([1 + uid, 2, 3], np.int32),
                           max_new_tokens=4))
    eng.run_until_done()
    assert len(eng.finished) == 5
    assert all(len(r.generated) == 4 for r in eng.finished)


def test_short_request_not_starved_by_long():
    """Length-aware packing: a short request queued behind a long one is
    grouped with its length peers instead of padding into the long wave's
    lockstep decode; admission stays FIFO within a bucket and the oldest
    request is always admitted (no starvation)."""
    eng = _engine(slots=2)
    long_a = Request(uid=0, prompt=np.arange(1, 13, dtype=np.int32),
                     max_new_tokens=12)
    short_b = Request(uid=1, prompt=np.array([1, 2], np.int32),
                      max_new_tokens=2)
    short_c = Request(uid=2, prompt=np.array([3, 4], np.int32),
                      max_new_tokens=2)
    long_d = Request(uid=3, prompt=np.arange(1, 13, dtype=np.int32),
                     max_new_tokens=12)
    for r in (long_a, short_b, short_c, long_d):
        eng.submit(r)
    eng.run_until_done()
    assert len(eng.finished) == 4
    assert all(len(r.generated) == r.max_new_tokens for r in eng.finished)
    # wave 1: the longs pack together (oldest request picks the bucket);
    # wave 2: the shorts share their own cheap wave
    assert eng.wave_log == [[0, 3], [1, 2]]


def test_fifo_within_bucket_and_oldest_first():
    """Uniform-length requests degrade to plain FIFO waves."""
    eng = _engine(slots=2)
    for uid in range(5):
        eng.submit(Request(uid=uid,
                           prompt=np.array([1 + uid, 2, 3], np.int32),
                           max_new_tokens=4))
    eng.run_until_done()
    assert eng.wave_log == [[0, 1], [2, 3], [4]]


def test_edf_admission_reorders_by_deadline():
    """qos="edf": the tightest effective deadline picks the wave bucket,
    so a late-submitted tight pair overtakes an early loose long pair."""
    eng = _engine(slots=2)
    eng.qos = "edf"
    loose_a = Request(uid=0, prompt=np.arange(1, 13, dtype=np.int32),
                      max_new_tokens=12, deadline=1000.0)
    loose_b = Request(uid=1, prompt=np.arange(1, 13, dtype=np.int32),
                      max_new_tokens=12, deadline=900.0)
    tight_c = Request(uid=2, prompt=np.array([1, 2], np.int32),
                      max_new_tokens=2, deadline=50.0)
    tight_d = Request(uid=3, prompt=np.array([3, 4], np.int32),
                      max_new_tokens=2, deadline=40.0)
    for r in (loose_a, loose_b, tight_c, tight_d):
        eng.submit(r)
    eng.run_until_done()
    # tight bucket first, EDF order inside each bucket
    assert eng.wave_log == [[3, 2], [1, 0]]
    assert len(eng.finished) == 4
    assert all(r.slack is not None and r.slack >= 0 for r in eng.finished)


def test_edf_aging_credit_prevents_cross_bucket_starvation():
    """A long-bucket request facing an endless stream of tight newcomers
    must still be admitted once its aging credit outweighs the deadline
    gap (co-submitted peers age together; the credit is earned against
    requests that arrive later)."""
    eng = _engine(slots=1, max_seq=64)
    eng.qos = "edf"
    eng.aging_credit = 8.0
    eng.shed = False
    long_r = Request(uid=0, prompt=np.arange(1, 13, dtype=np.int32),
                     max_new_tokens=12, deadline=200.0)
    eng.submit(long_r)
    waves = 0
    uid = 1
    while not long_r.done and waves < 40:
        # keep one tight short request arriving per wave, always with a
        # nearer absolute deadline than the long request's
        eng.submit(Request(uid=uid, prompt=np.array([1, 2], np.int32),
                           max_new_tokens=2, deadline=eng.clock + 50.0))
        uid += 1
        eng._run_wave(eng._next_wave())
        waves += 1
    assert long_r.done, "long request starved despite aging credit"
    # bound: (deadline spread)/credit waves of aging + one wave of grace
    spread = 200.0 - 50.0
    assert long_r.waves_waited <= spread / 8.0 + 2


def test_edf_timeout_shed_to_dead_letter():
    """A request whose decode budget cannot fit before its deadline is
    shed at admission, not served late."""
    eng = _engine(slots=2)
    eng.qos = "edf"
    doomed = Request(uid=0, prompt=np.array([1, 2, 3], np.int32),
                     max_new_tokens=8, deadline=2.0)  # needs 8 steps
    fine = Request(uid=1, prompt=np.array([1, 2, 3], np.int32),
                   max_new_tokens=4, deadline=500.0)
    # exact fit: finish lands at clock + max_new (prefill+first token is
    # one tick) — must be served with zero slack, not shed
    exact = Request(uid=2, prompt=np.array([1, 2, 3], np.int32),
                    max_new_tokens=4, deadline=4.0)
    for r in (doomed, fine, exact):
        eng.submit(r)
    eng.run_until_done()
    assert [r.uid for r in eng.dead_letter] == [0]
    assert sorted(r.uid for r in eng.finished) == [1, 2]
    assert exact.slack == pytest.approx(0.0)
    stats = eng.qos_stats()
    assert stats["shed"] == 1
    assert stats["miss_rate"] == pytest.approx(1 / 3)


def test_mixed_prompt_pricing_uses_wave_padding_aware_cap():
    """Truncation-pricing regression: a short prompt co-batched into a
    long-prompt wave decodes in lockstep from the wave's padded position,
    so ``max_seq`` can never deliver its naive per-request budget
    (``max_seq - own_prompt``).  Pricing and timeout shedding must use
    the wave-padding-aware cap: the old formula stamped the short request
    a deadline bought with 14 tokens it could never consume, and shed it
    against that same phantom need."""
    from repro.core.tasks import token_deadline_budget
    eng = _engine(slots=2, max_seq=16)
    eng.qos = "edf"
    long_r = Request(uid=0, prompt=np.arange(1, 13, dtype=np.int32),
                     max_new_tokens=4, deadline=500.0)   # bucket 16
    short_r = Request(uid=1, prompt=np.array([1, 2], np.int32),
                      max_new_tokens=14)                 # bucket 16 too
    eng.submit(long_r)
    eng.submit(short_r)
    # bucket 16 fills max_seq: only the prefill token is guaranteed
    assert short_r.priced_tokens == 1
    assert short_r.deadline == pytest.approx(token_deadline_budget(2, 1))
    assert short_r.deadline < token_deadline_budget(2, 14)  # old pricing
    eng.run_until_done()
    # old shed test needed 14 ticks -> clock 0 + 14 > deadline 6: shed a
    # request the wave serves by tick 4 with slack to spare
    assert not eng.dead_letter
    assert sorted(r.uid for r in eng.finished) == [0, 1]
    for r in eng.finished:  # delivery never falls below the priced budget
        assert len(r.generated) >= min(r.priced_tokens, r.max_new_tokens)
    stats = eng.qos_stats()
    assert stats["short_changed"] == 0
    assert short_r.slack is not None and short_r.slack >= 0.0


def test_token_cap_tight_at_full_bucket():
    """The cap's floor is exact: a request whose bucket equals max_seq
    gets precisely its one guaranteed (prefill) token, and a half-bucket
    request keeps the remaining headroom."""
    eng = _engine(slots=1, max_seq=16)
    full = Request(uid=0, prompt=np.arange(1, 16, dtype=np.int32),
                   max_new_tokens=1)                     # bucket 16
    half = Request(uid=1, prompt=np.array([1, 2, 3], np.int32),
                   max_new_tokens=5)                     # bucket 8
    for r in (full, half):
        eng.submit(r)
    assert full.priced_tokens == 1
    assert half.priced_tokens == 5                       # cap 9 >= 5
    eng.run_until_done()
    assert len(full.generated) == 1
    assert len(half.generated) == 5
    assert eng.qos_stats()["short_changed"] == 0


def test_default_deadline_derived_from_token_budget():
    """submit() stamps a Table-5-style per-token budget when no explicit
    deadline is given (tasks.token_deadline_budget)."""
    from repro.core.tasks import token_deadline_budget
    eng = _engine()
    r = Request(uid=0, prompt=np.array([1, 2, 3], np.int32),
                max_new_tokens=5)
    eng.submit(r)
    assert r.deadline == pytest.approx(token_deadline_budget(3, 5))
    assert r.deadline > 1 + r.max_new_tokens  # feasible by construction


def test_fifo_mode_never_sheds_and_logs_no_deadline_pressure():
    """Default engine (qos="fifo") behaves exactly as before: no dead
    letters, finish ordering by bucket-FIFO."""
    eng = _engine(slots=2)
    tight = Request(uid=0, prompt=np.array([1, 2, 3], np.int32),
                    max_new_tokens=4, deadline=0.5)  # impossibly tight
    eng.submit(tight)
    eng.run_until_done()
    assert not eng.dead_letter
    assert len(eng.finished) == 1
    assert eng.qos_stats()["miss_rate"] == 1.0  # late, but served


def test_greedy_decode_deterministic():
    eng1 = _engine()
    eng2 = _engine()
    for eng in (eng1, eng2):
        eng.submit(Request(uid=0, prompt=np.array([1, 2, 3], np.int32),
                           max_new_tokens=6))
        eng.run_until_done()
    assert eng1.finished[0].generated == eng2.finished[0].generated


def test_serve_step_sampled_path():
    """greedy=False must route through sample_token (the previously dead
    branch): temperature 0 reduces to the greedy argmax, temperature 1
    actually samples across seeds."""
    api = model_api(CFG)
    params = unbox(api.init(KEY))
    greedy_step = make_serve_step(api)
    argmax_step = make_serve_step(api, greedy=False, temperature=0.0)
    sampled_step = make_serve_step(api, greedy=False, temperature=1.0)
    tok = jnp.ones((2, 1), jnp.int32)
    pos = jnp.int32(0)

    def cache():
        return unbox(api.init_cache(2, 8))

    n_greedy, logits, _ = greedy_step(params, cache(), tok, pos)
    assert n_greedy.shape == (2, 1)
    np.testing.assert_array_equal(
        np.asarray(n_greedy[:, 0]),
        np.asarray(jnp.argmax(logits[:, -1, :], axis=-1)))
    n_zero, _, _ = argmax_step(params, cache(), tok, pos,
                               jax.random.PRNGKey(0))
    np.testing.assert_array_equal(np.asarray(n_zero), np.asarray(n_greedy))
    seen = {int(sampled_step(params, cache(), tok, pos,
                             jax.random.PRNGKey(s))[0][0, 0])
            for s in range(8)}
    assert len(seen) > 1


def test_sample_token_topk():
    logits = jnp.asarray([[0.0, 5.0, 1.0, 4.0]])
    t = sample_token(logits, jax.random.PRNGKey(0), temperature=0.0)
    assert int(t[0]) == 1
    for seed in range(10):
        t = sample_token(logits, jax.random.PRNGKey(seed), temperature=1.0,
                         top_k=2)
        assert int(t[0]) in (1, 3)


@pytest.mark.parametrize("route,area", [(0, "UB"), (1, "UHW"), (2, "HW"),
                                        (3, "UB"), (7, "UHW")])
def test_placement_fleet_areas_cycle_by_route(route, area):
    """A ``launch.serve --placement`` fleet mixes the paper's three areas
    by route index (UB, UHW, HW, UB, ...); every route has its own seed
    and the run's rate and length."""
    from repro.launch.serve import _route_params, parse_args
    args = parse_args(["--placement", "--routes", "8", "--seed", "5",
                       "--rate-scale", "0.5", "--route-km", "0.02"])
    p = _route_params(args, route)
    assert p.area.value == area and p.seed == 5 + route
    assert p.rate_scale == 0.5 and p.route_km == 0.02
