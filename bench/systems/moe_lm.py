"""A served MoE decoder: ``ServeEngine`` driven by a request mix.

``build`` makes the weights from the seed (``moe_lm_ref.make_weights``, on
the device in one jitted call), builds the engine at the traffic's slot
count and cache length, and warms it: a first wave of requests that fills
every slot, whose first prompts cover each prefill bucket the traffic
names (``warm_prompts``), stepped ``warm_steps`` times.  The traffic is a
closed loop: the first wave's output lengths are staggered (client ``c`` of
``n`` gets ``(c+1)/n`` of its drawn length), so completions are spread as in
a loop that has run for a while and the window starts near its steady
state.

The window steps the engine until ``--seconds`` have passed.  Each step is
accounted from the requests alone: tokens served, prefills (a request's
first token) and the decode step's attended lengths, each counted in
operations and bytes by ``work.py``.  A client whose request finished sends
its next one between steps.

``readings``: once the window has closed and the engine is freed, a sample
drawn from the seed of the requests finished in the window (the one with
most served tokens first, then until ``check_tokens`` served tokens) is run
through the plain reference over prompt + served tokens.  For each served
token it reads the gap by which its reference logit lies below the
reference's best at that position, in units of the standard deviation of
the reference's logits there (so it does not depend on the scale of the
random weights).  Reported: the widest gap, the mean, the 99th percentile
and the share of gaps over 0.1; the configuration's ``limits`` say which
are compared.
"""

from __future__ import annotations

import gc
import time
from typing import Dict, List

import jax
import numpy as np

from bench import loadgen, seeds, work
from bench.systems import moe_lm_ref as ref

__all__ = ["build", "lm_shape", "model_config"]

TA = jax.profiler.TraceAnnotation


def build(config: Dict, traffic: Dict, seed: int, log):
    return Serve(config, traffic, seed, log)


def lm_shape(c: Dict) -> work.LMShape:
    return work.LMShape(
        n_layers=c["num_hidden_layers"], d_model=c["hidden_size"],
        n_heads=c["num_attention_heads"], n_kv_heads=c["num_key_value_heads"],
        head_dim=c["head_dim"], experts=c["num_local_experts"],
        topk=c["num_experts_per_tok"], expert_ff=c["intermediate_size"],
        vocab=c["vocab_size"])


def model_config(c: Dict):
    """The program's ``ModelConfig`` for the configuration file."""
    from repro.models.config import ModelConfig
    return ModelConfig(
        name=c["name"], family="moe", n_layers=c["num_hidden_layers"],
        d_model=c["hidden_size"], n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"], d_ff=0,
        vocab=c["vocab_size"], moe_experts=c["num_local_experts"],
        moe_topk=c["num_experts_per_tok"], moe_dff=c["intermediate_size"],
        moe_capacity=float(c["moe_capacity"]), rope_theta=c["rope_theta"],
        norm_eps=c["rms_norm_eps"], dtype=c["torch_dtype"],
        moe_dispatch=c["moe_dispatch"])


class Serve:
    def __init__(self, config: Dict, traffic: Dict, seed: int, log):
        self.config, self.traffic, self.log = config, traffic, log
        self.shape = lm_shape(config)
        if traffic["arrival"]["kind"] != "closed":
            raise ValueError("the serving driver runs closed loops only")
        self.clients = int(traffic["arrival"]["clients"])
        self.reseed(seed)

    def reseed(self, seed: int) -> None:
        """Weights, engine and request stream anew from ``seed``, warmed."""
        from repro.serving.engine import ServeEngine
        self.engine = self.params = None
        gc.collect()
        self.seed = seed
        t = time.perf_counter()
        self.params = jax.block_until_ready(
            ref.make_weights(self.config, seeds.jax_key(seed, 3)))
        nbytes = sum(int(p.nbytes) for p in jax.tree.leaves(self.params))
        self.log(f"weights {nbytes} bytes from the seed in "
                 f"{time.perf_counter() - t:.6f} s")
        self.engine = ServeEngine(model_config(self.config), self.params,
                                  batch=self.traffic["slots"],
                                  s_max=self.traffic["s_max"], greedy=True)
        self.stream = loadgen.RequestStream(self.traffic, seed,
                                            self.config["vocab_size"])
        self.rid = 0
        self.client_of: Dict[int, int] = {}
        self.inflight: Dict[int, object] = {}     # rid -> Request
        self.seen: Dict[int, int] = {}            # rid -> tokens accounted
        self.all: List = []
        t = time.perf_counter()
        self._warm()
        self.log(f"serving warm-up {time.perf_counter() - t:.6f} s "
                 f"({self.engine.steps} steps)")

    # ------------------------------------------------------------- requests
    def _submit(self, client: int, prompt_len: int = 0, frac: float = 1.0):
        from repro.serving.engine import Request
        toks, n_out = self.stream.next(prompt_len)
        req = Request(rid=self.rid, tokens=toks,
                      max_new=max(1, int(round(n_out * frac))))
        self.client_of[req.rid] = client
        self.inflight[req.rid] = req
        self.seen[req.rid] = 0
        self.all.append(req)
        self.rid += 1
        self.engine.submit(req)

    def _warm(self) -> None:
        warm = list(self.traffic["warm_prompts"])
        n = self.clients
        for c in range(n):
            plen = warm[c] if c < len(warm) else 0
            self._submit(c, plen, (c + 1) / n)
        while self.engine.steps < int(self.traffic["warm_steps"]):
            self._step(account=None)

    def _step(self, account) -> None:
        with TA("serve.step"):
            self.engine.step()
        with TA("serve.submit"):
            ctxs = []
            for rid, req in list(self.inflight.items()):
                before, after = self.seen[rid], len(req.out)
                if after == before:
                    continue
                self.seen[rid] = after
                if account is not None:
                    account["tokens"] += after - before
                    if before == 0:
                        account["units"].append(
                            work.lm_prefill(self.shape, req.prompt_len))
                        account["prefills"] += 1
                    if after - before > (before == 0):
                        ctxs.append(req.prompt_len + after - 1)
                if req.done:
                    del self.inflight[rid]
                    self._submit(self.client_of[rid])
            if account is not None and ctxs:
                account["units"].append(work.lm_decode_step(self.shape, ctxs))
                account["decode_steps"] += 1

    # --------------------------------------------------------------- window
    def window(self, seconds: float) -> Dict:
        acc = {"tokens": 0, "prefills": 0, "decode_steps": 0, "units": []}
        t0 = time.perf_counter()
        while True:
            self._step(acc)
            if time.perf_counter() - t0 >= seconds:
                break
        t1 = time.perf_counter()
        ttft = [r.t_first - r.t_submit for r in self.all
                if t0 <= r.t_first <= t1]
        self.done_in_window = [r for r in self.all
                               if r.done and t0 <= r.t_last <= t1]
        units = acc.pop("units")
        total = sum(units, work.Work(0.0, 0.0))
        return {"t0": t0, "t1": t1, "attempted": len(self.done_in_window),
                "tokens": acc["tokens"], "ttft_s": ttft, "serve_units": units,
                "counts": dict(acc, requests_finished=len(self.done_in_window),
                               first_tokens=len(ttft), flops=total.flops,
                               bytes=total.bytes)}

    # ----------------------------------------------------------- comparison
    def _sample(self) -> List:
        done = sorted(self.done_in_window, key=lambda r: -len(r.out))
        if not done:
            return []
        pick, rest = [done[0]], done[1:]
        order = seeds.rng(self.seed, 6).permutation(len(rest))
        total = len(done[0].out)
        for i in order:
            if total >= self.traffic["check_tokens"]:
                break
            pick.append(rest[i])
            total += len(rest[i].out)
        return pick

    def readings(self, with_control: bool) -> Dict:
        sample = [(list(r.tokens), list(r.out)) for r in self._sample()]
        self.engine = self.params = None          # free the program's state
        gc.collect()
        t = time.perf_counter()
        params = ref.make_weights(self.config, seeds.jax_key(self.seed, 3))
        s_pad = self.traffic["prompt"]["max"] + self.traffic["output"]["max"]
        gaps, ctl_gaps, served = [], [], 0
        for prompt, out in sample:
            seq = np.zeros(s_pad, np.int32)
            full = prompt + out[:-1]
            seq[:len(full)] = full
            pos = np.arange(len(prompt) - 1, len(full))
            want = np.asarray(ref.forward(params, seq, self.config))[pos]
            best, unit = want.max(axis=1), want.std(axis=1)
            gaps.append((best - want[np.arange(len(out)), out]) / unit)
            served += len(out)
            if with_control:
                got = np.asarray(ref.forward(params, seq, self.config,
                                             quant="fp8"))[pos]
                pick = got.argmax(axis=1)
                ctl_gaps.append((best - want[np.arange(len(out)), pick])
                                / unit)
        self.log(f"checked {len(sample)} requests, {served} served tokens, "
                 f"in {time.perf_counter() - t:.6f} s")
        out = {"program": self._numbers(gaps), "control": None}
        if with_control:
            out["control"] = self._numbers(ctl_gaps)
        return out

    @staticmethod
    def _numbers(gaps: List[np.ndarray]) -> Dict:
        g = np.concatenate(gaps) if gaps else np.asarray([np.inf])
        return {"serve_max_logit_gap": float(g.max()),
                "serve_mean_logit_gap": float(g.mean()),
                "serve_p99_logit_gap": float(np.percentile(g, 99)),
                "serve_share_gap_over_0.1": float(np.mean(g > 0.1))}
