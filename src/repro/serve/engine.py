"""Serving: jit-able decode/prefill steps + a batched continuous-batching
engine.

``make_serve_step`` is what the decode-shape dry-run cells lower: one new
token against a KV cache of the cell's sequence length, cache donated so the
update is in-place.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.api import ModelAPI


def make_serve_step(api: ModelAPI, greedy: bool = True,
                    temperature: float = 1.0, top_k: int = 0):
    """(params, cache, token [B,1], pos scalar) -> (next_token, logits, cache).

    With ``greedy=False`` the step takes a trailing PRNG ``key`` argument
    and samples through :func:`sample_token` (temperature / top-k).
    """

    def serve_step(params, cache, token, pos):
        logits, new_cache = api.decode_step(params, cache, token, pos)
        nxt = jnp.argmax(logits[:, -1, :], axis=-1).astype(jnp.int32)
        return nxt[:, None], logits, new_cache

    def sampled_step(params, cache, token, pos, key):
        logits, new_cache = api.decode_step(params, cache, token, pos)
        nxt = sample_token(logits[:, -1, :], key, temperature=temperature,
                           top_k=top_k)
        return nxt[:, None], logits, new_cache

    return serve_step if greedy else sampled_step


def make_prefill_step(api: ModelAPI):
    def prefill_step(params, batch):
        return api.prefill(params, batch)

    return prefill_step


def sample_token(logits: jax.Array, key, temperature: float = 1.0,
                 top_k: int = 0):
    """logits [B, V] -> tokens [B]."""
    if temperature <= 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    logits = logits / temperature
    if top_k:
        vals, _ = jax.lax.top_k(logits, top_k)
        cutoff = vals[:, -1:]
        logits = jnp.where(logits < cutoff, -1e30, logits)
    return jax.random.categorical(key, logits).astype(jnp.int32)


@dataclasses.dataclass
class Request:
    uid: int
    prompt: np.ndarray  # [S] int32
    max_new_tokens: int
    generated: list = dataclasses.field(default_factory=list)
    done: bool = False
    # deadline-aware QoS (engine step units; see serve.qos for the
    # placement-side analogue).  ``deadline`` is absolute; None at submit
    # means "derive from the token budget" (tasks.token_deadline_budget).
    deadline: "float | None" = None
    submit_time: float = 0.0
    finish_time: "float | None" = None
    waves_waited: int = 0
    # decode tokens the admission pricing promised (wave-padding-aware
    # cap applied); delivery below this is a pricing bug, not truncation
    priced_tokens: "int | None" = None

    @property
    def slack(self) -> "float | None":
        if self.deadline is None or self.finish_time is None:
            return None
        return self.deadline - self.finish_time

    @property
    def submit_order(self) -> int:
        # QoSPolicy sort-key protocol (ties inside one wave break on uid)
        return self.uid


class ServeEngine:
    """Wave-based batched serving with a static decode shape.

    Requests are admitted in waves of ``slots``: a wave's prompts are padded
    to a common length, batch-prefilled once, then decoded in lockstep until
    every request in the wave finishes (per-request EOS/max handled with a
    done mask).  The decode step keeps a single static (batch, cache) shape —
    the property the compiled/sharded step needs on real hardware.  When a
    wave drains, the next wave is admitted (continuous batching at wave
    granularity).

    Admission is length-aware rather than strict FIFO: a wave's cost is its
    *longest* member (lockstep decode + common prompt padding), so queued
    requests are bucketed by total length (prompt + budget, power-of-two)
    and each wave greedily packs the bucket of the oldest queued request —
    FIFO across waves at head granularity (no starvation: the oldest
    request is always admitted) and FIFO within a bucket, but a short
    request queued behind a long one rides a short wave instead of paying
    the long wave's decode steps.  ``wave_log`` records the admitted uid
    groups for observability/tests.

    ``qos="edf"`` makes admission deadline-aware: the head is the earliest
    *effective* deadline (deadline minus ``aging_credit`` per passed-over
    wave), buckets drain in effective-deadline order, and requests whose
    decode budget can no longer fit before their deadline are shed to
    ``dead_letter`` instead of served late.  Deadlines default to the
    per-token budget of ``tasks.token_deadline_budget`` on the engine's
    virtual step clock (1.0 per decode step, so QoS decisions are
    deterministic).  ``serve.qos`` holds the placement-side analogue with
    preemption; ``qos_stats()`` reports miss rate and slack percentiles.
    """

    def __init__(self, api: ModelAPI, params, *, slots: int, max_seq: int,
                 temperature: float = 0.0, seed: int = 0,
                 pad_token: int = 0, qos: str = "fifo",
                 deadline_scale: float = 1.0, aging_credit: float = 4.0,
                 shed: bool = True):
        from repro.serve.policy import QoSPolicy
        if qos not in ("fifo", "edf"):
            raise ValueError(f"unknown qos policy {qos!r}")
        self._qpolicy: "QoSPolicy | None" = None
        self.api = api
        self.params = params
        self.slots = slots
        self.max_seq = max_seq
        self.temperature = temperature
        self.pad_token = pad_token
        self.qos = qos
        self.deadline_scale = deadline_scale
        self.aging_credit = aging_credit
        self.shed = shed
        self.key = jax.random.PRNGKey(seed)
        self.queue: list[Request] = []
        self.finished: list[Request] = []
        self.dead_letter: list[Request] = []
        self._decode = jax.jit(api.decode_step, donate_argnums=(1,))
        self._prefill = jax.jit(api.prefill)
        self.steps_executed = 0
        self.clock = 0.0          # virtual step clock (1.0 per decode step)
        self.wave_log: list[list[int]] = []

    @property
    def qpolicy(self):
        """The shared EDF/aging/shed formula object (serve.policy) —
        rebuilt lazily so the ``qos`` / ``aging_credit`` / ``shed``
        attributes stay live knobs (tests flip them post-construction)."""
        from repro.serve.policy import QoSPolicy
        p = self._qpolicy
        if (p is None or p.policy != self.qos
                or p.aging_credit != self.aging_credit
                or p.shed != self.shed):
            p = QoSPolicy(policy=self.qos, aging_credit=self.aging_credit,
                          shed=self.shed)
            self._qpolicy = p
        return p

    def _token_cap(self, req: Request) -> int:
        """Decode tokens ``max_seq`` can guarantee this request *in a
        wave*: co-batched peers share the request's power-of-two length
        bucket, so the wave's common prompt padding can push ``pos`` up
        to ``bucket - 1`` before the first decode step.  Capping by the
        request's own prompt length (the old formula) over-promised a
        short prompt co-batched with a long one — it was priced and
        shed-tested for tokens the lockstep decode loop could never
        reach (ISSUE 10 bugfix).  Any bucket peer keeps >= 1 token of
        budget, so the wave's prompt length is at most ``bucket - 1``
        and this bound is tight."""
        return 1 + max(0, self.max_seq - self._length_bucket(req))

    def submit(self, req: Request) -> None:
        from repro.core.tasks import token_deadline_budget
        req.submit_time = self.clock
        # price the deadline for the tokens a wave can actually deliver,
        # so a truncated request cannot buy easy slack from a budget it
        # will never consume
        req.priced_tokens = min(req.max_new_tokens, self._token_cap(req))
        if req.deadline is None:
            req.deadline = self.clock + token_deadline_budget(
                len(req.prompt), req.priced_tokens, self.deadline_scale)
        self.queue.append(req)

    def _merge_cache(self, prefill_cache):
        """Embed the prefill-length cache into a max_seq-length zero cache.

        KV entries get written at sequence offset 0 (positions 0..plen-1);
        SSM states match shape exactly and pass through.
        """
        from repro.sharding import unbox
        zero = unbox(self.api.init_cache(self.slots, self.max_seq))

        def merge(z, p):
            if z.shape == p.shape:
                return p.astype(z.dtype)
            # KV entries: [..., S, ...] differ only in the seq dim (axis 2)
            if (z.ndim == p.ndim and z.shape[:2] == p.shape[:2]
                    and z.shape[3:] == p.shape[3:]
                    and p.shape[2] <= z.shape[2]):
                return jax.lax.dynamic_update_slice(
                    z, p.astype(z.dtype), (0,) * z.ndim)
            raise ValueError(f"cache merge mismatch: {z.shape} vs {p.shape}")

        return jax.tree_util.tree_map(merge, zero, prefill_cache)

    @staticmethod
    def _length_bucket(req: Request) -> int:
        """Power-of-two bucket of the request's total token budget — the
        quantity that sets its wave's lockstep cost."""
        from repro.serve.qos import power_of_two_bucket
        return power_of_two_bucket(
            max(len(req.prompt) + req.max_new_tokens, 1), 1)

    def _eff_deadline(self, req: Request) -> float:
        """EDF comparison key (shared object: serve.policy.QoSPolicy —
        the placement engine and this token engine must never drift)."""
        return self.qpolicy.eff_deadline(req.deadline, req.waves_waited)

    def _shed_overdue(self) -> None:
        """Timeout shedding: a queued request that cannot finish its decode
        budget before its deadline moves to the dead-letter log."""
        keep = []
        for req in self.queue:
            # finish lands at clock + priced ticks (the prefill+first-token
            # tick covers token 1, then priced - 1 decode ticks) — the
            # wave-bucket-aware cap applied at submit
            need = float(max(min(req.max_new_tokens, self._token_cap(req)),
                             1))
            if self.qpolicy.should_shed(self.clock, need, req.deadline):
                req.finish_time = self.clock
                self.dead_letter.append(req)
            else:
                keep.append(req)
        self.queue = keep

    def _next_wave(self) -> list[Request]:
        # greedy bin-pack: the head request picks the wave's length bucket,
        # then the wave fills from that bucket (slots not fillable from the
        # bucket stay padded — mixing buckets would stretch every short
        # member to the longest).  Under "fifo" the head is the oldest
        # request and the bucket drains in submit order (the pre-QoS
        # engine); under "edf" the head is the earliest effective deadline
        # and the bucket drains in effective-deadline order, with every
        # passed-over request earning one wave of aging credit.
        if self.qos == "edf":
            if self.shed:
                self._shed_overdue()
            if not self.queue:
                return []
            head = min(self.queue, key=self.qpolicy.request_key)
            bucket = self._length_bucket(head)
            peers = sorted(
                (r for r in self.queue if self._length_bucket(r) == bucket),
                key=self.qpolicy.request_key)
            wave = peers[: self.slots]
            taken = {id(r) for r in wave}
            self.queue = [r for r in self.queue if id(r) not in taken]
            self.qpolicy.age(self.queue)
        else:
            bucket = self._length_bucket(self.queue[0])
            wave, rest = [], []
            for req in self.queue:
                if (len(wave) < self.slots
                        and self._length_bucket(req) == bucket):
                    wave.append(req)
                else:
                    rest.append(req)
            self.queue = rest
        self.wave_log.append([r.uid for r in wave])
        while len(wave) < self.slots:  # pad the wave with dummy requests
            wave.append(Request(uid=-1, prompt=np.array([self.pad_token],
                                                        np.int32),
                                max_new_tokens=0, done=True))
        return wave

    def _run_wave(self, wave: list[Request]) -> None:
        plen = max(len(r.prompt) for r in wave)
        prompts = np.full((self.slots, plen), self.pad_token, np.int32)
        for i, r in enumerate(wave):
            prompts[i, plen - len(r.prompt):] = r.prompt  # left-pad
        batch = {"tokens": jnp.asarray(prompts)}
        if self.api.cfg.frontend is not None:
            t = max(1, self.api.cfg.num_frontend_tokens)
            batch["frontend_embeds"] = jnp.zeros(
                (self.slots, t, self.api.cfg.d_model), jnp.float32)
        logits, prefill_cache = self._prefill(self.params, batch)
        cache = self._merge_cache(prefill_cache)
        self.key, sub = jax.random.split(self.key)
        tok = np.asarray(sample_token(logits[:, -1, :], sub,
                                      self.temperature))[:, None]
        pos = plen
        self.clock += 1.0  # prefill + first sampled token
        max_new = max((r.max_new_tokens for r in wave), default=0)
        for i, r in enumerate(wave):
            if not r.done and r.max_new_tokens > 0:
                r.generated.append(int(tok[i, 0]))
            if not r.done and len(r.generated) >= r.max_new_tokens:
                r.done = True
                r.finish_time = self.clock
        for _ in range(max_new - 1):
            if pos >= self.max_seq - 1:
                break
            logits, cache = self._decode(self.params, cache,
                                         jnp.asarray(tok), jnp.int32(pos))
            self.steps_executed += 1
            self.clock += 1.0
            self.key, sub = jax.random.split(self.key)
            tok = np.asarray(sample_token(logits[:, -1, :], sub,
                                          self.temperature))[:, None]
            pos += 1
            for i, r in enumerate(wave):
                if not r.done and len(r.generated) < r.max_new_tokens:
                    r.generated.append(int(tok[i, 0]))
                if not r.done and len(r.generated) >= r.max_new_tokens:
                    r.done = True
                    r.finish_time = self.clock
        for r in wave:
            r.done = True
            if r.finish_time is None:
                r.finish_time = self.clock
            if r.uid >= 0:
                self.finished.append(r)

    def run_until_done(self, max_waves: int = 1000) -> None:
        for _ in range(max_waves):
            if not self.queue:
                return
            wave = self._next_wave()
            if not wave:      # queue fully shed at admission
                return
            self._run_wave(wave)

    def qos_stats(self) -> dict:
        """Deadline bookkeeping over everything served so far (resolved
        requests only — the shared ``QoSPolicy.miss_stats`` contract)."""
        ms = self.qpolicy.miss_stats([r.slack for r in self.finished],
                                     len(self.dead_letter))
        return {
            "policy": self.qos,
            "finished": len(self.finished),
            "queued": len(self.queue),
            "shed": ms["shed"],
            # requests cut short by max_seq got partial service; they are
            # reported separately rather than silently counted as met
            "truncated": sum(1 for r in self.finished
                             if len(r.generated) < r.max_new_tokens),
            # delivery below the priced budget would mean admission and
            # the lockstep decode loop disagree again — pinned at 0 by
            # the mixed-prompt regression test
            "short_changed": sum(
                1 for r in self.finished
                if r.priced_tokens is not None
                and len(r.generated) < min(r.priced_tokens,
                                           r.max_new_tokens)),
            "missed_deadline": ms["missed_deadline"],
            "miss_rate": ms["miss_rate"],
            "p50_slack": ms["p50_slack"],
            "p99_slack": ms["p99_slack"],
            "mean_turnaround": float(np.mean(
                [r.finish_time - r.submit_time for r in self.finished]))
            if self.finished else 0.0,
        }
