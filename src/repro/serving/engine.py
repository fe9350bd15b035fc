"""Continuous-batching serving engine with bucketed prefill and SLO metrics.

``ServeEngine`` owns one fixed-size decode batch of slots.  Requests queue;
whenever a slot frees (EOS or length), the next request is prefilled into it
(prefill writes its KV into that slot's cache rows) while the other slots
keep decoding — continuous batching, not static batching.  All active slots
step together through one jitted decode program per token — the standard
TPU serving shape.

Compiled programs are capacity plans: like the MoE dispatch plans (see
:mod:`repro.core.dynplan`), the engine hashes the *static* part of each
problem and reuses the cached executable for the dynamic rest.  Prompt
lengths are bucketed to the next power of two (right-padded; causal masking
keeps real positions numerically unaffected, and decode overwrites each pad
KV row before its mask exposes it), so the prefill program cache holds at
most ``log2(s_max)`` entries under arbitrary-length traffic instead of one
per distinct prompt length.  The shared :class:`repro.core.PlanCache`
hit/miss counters feed ``BENCH_serving.json``.

Per-request service metrics follow the serving literature: TTFT (submit →
first token), TPOT (mean inter-token time after the first), and SLO
attainment against configurable targets — aggregated by :meth:`metrics`.
See :mod:`repro.serving.loadgen` for the open-loop synthetic load driver.
"""

from __future__ import annotations

import dataclasses
import time
from functools import partial
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..core import sflog
from ..core.dynplan import PlanCache
from ..models import transformer as T
from ..models.config import ModelConfig

__all__ = ["Request", "ServeEngine", "next_pow2"]


def next_pow2(n: int) -> int:
    """Smallest power of two >= n (the prefill length bucket)."""
    return 1 << max(int(n) - 1, 0).bit_length()


@jax.jit
def serve_cache_insert(cache: Dict, cache1: Dict, slot) -> Dict:
    """Copy a batch-1 prefill cache into row ``slot`` of the engine cache:
    every per-slot entry ((L, B, ...): GQA's ``k``/``v``, MLA's latent
    ``ckv``/``kpe``, hymba's SSM state ``h``, of each layer stack); the
    scalar ``pos`` stays the engine's."""
    with sflog.scope("serve.cache_insert"):
        out = dict(cache)
        for name, c in cache.items():
            if c.ndim >= 2 and name in cache1:
                out[name] = jax.lax.dynamic_update_index_in_dim(
                    c, cache1[name][:, 0].astype(c.dtype), slot, axis=1)
        return out


@jax.jit
def serve_sample_greedy(logits: jnp.ndarray) -> jnp.ndarray:
    with sflog.scope("serve.sample"):
        return jnp.argmax(logits, axis=-1)


@partial(jax.jit, static_argnames="temperature")
def serve_sample(key, logits: jnp.ndarray, *, temperature: float
                 ) -> jnp.ndarray:
    with sflog.scope("serve.sample"):
        return jax.random.categorical(key, logits / temperature, axis=-1)


@dataclasses.dataclass
class Request:
    rid: int
    tokens: List[int]
    max_new: int = 32
    out: List[int] = dataclasses.field(default_factory=list)
    done: bool = False
    # service timeline (engine clock seconds; -1 = not yet)
    t_submit: float = -1.0
    t_first: float = -1.0
    t_last: float = -1.0

    @property
    def prompt_len(self) -> int:
        return len(self.tokens)

    @property
    def ttft(self) -> Optional[float]:
        """Time to first token (s), once it exists."""
        if self.t_first < 0 or self.t_submit < 0:
            return None
        return self.t_first - self.t_submit

    @property
    def tpot(self) -> Optional[float]:
        """Mean time per output token after the first (s)."""
        if self.t_first < 0 or self.t_last < 0 or len(self.out) < 2:
            return None
        return (self.t_last - self.t_first) / (len(self.out) - 1)


class ServeEngine:
    def __init__(self, cfg: ModelConfig, params, *, batch: int = 8,
                 s_max: int = 512, eos_id: Optional[int] = None,
                 greedy: bool = True, temperature: float = 1.0, seed: int = 0,
                 bucket_prompts: Optional[bool] = None,
                 ttft_slo: Optional[float] = None,
                 tpot_slo: Optional[float] = None,
                 clock=time.perf_counter):
        if cfg.block_kind == "xlstm":
            raise NotImplementedError(
                "slot-wise cache insert for recurrent archs: serve xlstm via "
                "examples/serve_lm.py --arch with uniform batches")
        self.cfg = cfg
        self.params = params
        self.batch = batch
        self.s_max = s_max
        self.eos_id = eos_id
        self.greedy = greedy
        self.temperature = temperature
        self.key = jax.random.PRNGKey(seed)
        # hymba's SSM state is sequential — pad tokens at the tail would
        # corrupt it, so bucketing is attention-cache archs only
        if bucket_prompts is None:
            bucket_prompts = cfg.block_kind == "transformer"
        self.bucket_prompts = bucket_prompts
        self.ttft_slo = ttft_slo
        self.tpot_slo = tpot_slo
        self.clock = clock

        self.cache = T.init_cache(cfg, batch, s_max)
        # slot-local decode position (cache['pos'] is per-batch scalar in the
        # single-stream path; the engine keeps per-slot positions and uses
        # the masked decode below)
        self.positions = np.zeros(batch, dtype=np.int32)
        self.active: List[Optional[Request]] = [None] * batch
        self.queue: List[Request] = []
        self.finished: List[Request] = []
        self.t_start: Optional[float] = None
        # service tallies live in the sflog registry (per-engine counters);
        # .steps stays a readable/assignable attribute via the property below
        self._c_steps = sflog.counter("serve.decode_steps", unique=True)
        self._c_tokens = sflog.counter("serve.tokens_generated", unique=True)
        self._c_ttft_n = sflog.counter("serve.ttft_slo_total", unique=True)
        self._c_ttft_ok = sflog.counter("serve.ttft_slo_ok", unique=True)
        self._c_tpot_n = sflog.counter("serve.tpot_slo_total", unique=True)
        self._c_tpot_ok = sflog.counter("serve.tpot_slo_ok", unique=True)

        # compiled-program cache: ("prefill", bucket) / ("decode", batch)
        self.programs = PlanCache("serve-programs")

    @property
    def steps(self) -> int:
        return self._c_steps.value

    @steps.setter
    def steps(self, v: int) -> None:
        self._c_steps.value = int(v)

    # -------------------------------------------------------------- prefill
    def _bucket(self, plen: int) -> int:
        if not self.bucket_prompts:
            return plen
        return min(next_pow2(plen), self.s_max)

    def _prefill_fn(self, bucket: int):
        cfg, s_max = self.cfg, self.s_max

        def build():
            def serve_prefill(params, tokens, last_pos):
                with sflog.scope("serve.prefill"):
                    return T.prefill(params, cfg, tokens=tokens,
                                     s_max=s_max, last_pos=last_pos)
            return jax.jit(serve_prefill)
        return self.programs.get_or_build(("prefill", bucket), build)

    def _decode_fn(self):
        cfg = self.cfg

        def build():
            def serve_decode(params, tokens, cache, positions):
                with sflog.scope("serve.decode"):
                    return self._decode_impl(cfg, params, tokens, cache,
                                             positions)
            return jax.jit(serve_decode)
        return self.programs.get_or_build(("decode", self.batch), build)

    @staticmethod
    def _decode_impl(cfg, params, tokens, cache, positions):
        """Per-slot-position decode (each batch row at its own position):
        the model's slot decode, GQA or MLA by the block's kind."""
        return T.decode_slots(params, cfg, tokens, cache, positions)

    # ------------------------------------------------------------- plumbing
    def submit(self, req: Request):
        if req.t_submit < 0:
            req.t_submit = self.clock()
        self.queue.append(req)

    def _admit(self):
        for slot in range(self.batch):
            if self.active[slot] is None and self.queue:
                req = self.queue.pop(0)
                plen = len(req.tokens)
                bucket = self._bucket(plen)
                toks = np.zeros((1, bucket), np.int32)
                toks[0, :plen] = req.tokens
                with sflog.span("serve.prefill", rid=req.rid, bucket=bucket):
                    logits, cache1 = self._prefill_fn(bucket)(
                        self.params, jnp.asarray(toks),
                        jnp.asarray([plen - 1], np.int32))
                # copy slot rows into the engine cache
                with sflog.span("serve.cache_insert"):
                    self.cache = serve_cache_insert(
                        self.cache, cache1, np.int32(slot))
                first = int(self._sample(logits)[0])
                req.out.append(first)
                self._c_tokens.add(1)
                req.t_first = req.t_last = self.clock()
                self.positions[slot] = plen
                self.active[slot] = req

    def _sample(self, logits: jnp.ndarray) -> np.ndarray:
        """Next tokens, read back to the host (the step's sync)."""
        with sflog.span("serve.sample"):
            if self.greedy:
                return np.asarray(serve_sample_greedy(logits), np.int32)
            self.key, sub = jax.random.split(self.key)
            return np.asarray(serve_sample(sub, logits,
                                           temperature=self.temperature),
                              np.int32)

    def step(self) -> int:
        """Admit + one decode step for all active slots.  Returns #pending
        (active slots + queued requests)."""
        if self.t_start is None:
            self.t_start = self.clock()
        with sflog.span("serve.admit"):
            self._admit()
        if not any(r is not None for r in self.active):
            return len(self.queue)
        last = np.zeros(self.batch, np.int32)
        for s, r in enumerate(self.active):
            if r is not None:
                last[s] = r.out[-1] if r.out else r.tokens[-1]
        with sflog.span("serve.decode", step=self.steps):
            logits, self.cache = self._decode_fn()(
                self.params, jnp.asarray(last), self.cache,
                jnp.asarray(self.positions))
        nxt = self._sample(logits)
        self._c_steps.add(1)
        now = self.clock()
        n_active = 0
        for s, r in enumerate(self.active):
            if r is None:
                continue
            tok = int(nxt[s])
            r.out.append(tok)
            self._c_tokens.add(1)
            r.t_last = now
            self.positions[s] += 1
            hit_eos = self.eos_id is not None and tok == self.eos_id
            if hit_eos or len(r.out) >= r.max_new or \
                    self.positions[s] >= self.s_max - 1:
                r.done = True
                self._finish_tallies(r)
                self.finished.append(r)
                self.active[s] = None
            else:
                n_active += 1
        return n_active + len(self.queue)

    def run(self, requests: List[Request]) -> List[Request]:
        for r in requests:
            self.submit(r)
        while self.step():
            pass
        return requests

    # -------------------------------------------------------------- metrics
    def _finish_tallies(self, r: Request) -> None:
        """Registry-side SLO tallies, bumped once per finished request."""
        if self.ttft_slo is not None and r.ttft is not None:
            self._c_ttft_n.add(1)
            if r.ttft <= self.ttft_slo:
                self._c_ttft_ok.add(1)
        if self.tpot_slo is not None and r.tpot is not None:
            self._c_tpot_n.add(1)
            if r.tpot <= self.tpot_slo:
                self._c_tpot_ok.add(1)

    def metrics(self) -> Dict:
        """Aggregate service metrics over finished requests: tokens/sec,
        TTFT/TPOT p50/p99, SLO attainment, program-cache stats."""
        done = self.finished

        def pct(vals, q):
            return float(np.percentile(vals, q)) if vals else None

        ttfts = [r.ttft for r in done if r.ttft is not None]
        tpots = [r.tpot for r in done if r.tpot is not None]
        gen = sum(len(r.out) for r in done) + \
            sum(len(r.out) for r in self.active if r is not None)
        t_end = max([self.t_start or 0.0] +
                    [r.t_last for r in done if r.t_last >= 0])
        elapsed = max(t_end - self.t_start, 1e-9) if self.t_start is not None \
            else None
        out = {
            "requests_finished": len(done),
            "decode_steps": self.steps,
            "tokens_generated": gen,
            "tokens_per_sec": (gen / elapsed) if elapsed else None,
            "ttft_p50_s": pct(ttfts, 50), "ttft_p99_s": pct(ttfts, 99),
            "tpot_p50_s": pct(tpots, 50), "tpot_p99_s": pct(tpots, 99),
            "program_cache": self.programs.stats(),
            "prefill_buckets": sorted(k[1] for k in self.programs.keys()
                                      if k[0] == "prefill"),
        }
        if self.ttft_slo is not None and ttfts:
            out["ttft_slo_s"] = self.ttft_slo
            out["ttft_slo_attainment"] = float(
                np.mean([t <= self.ttft_slo for t in ttfts]))
        if self.tpot_slo is not None and tpots:
            out["tpot_slo_s"] = self.tpot_slo
            out["tpot_slo_attainment"] = float(
                np.mean([t <= self.tpot_slo for t in tpots]))
        return out
