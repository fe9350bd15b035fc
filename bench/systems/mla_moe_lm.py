"""A served latent-attention MoE decoder (the DeepSeek-V3 block of
Kimi-K2): ``ServeEngine`` driven by a request mix, as ``moe_lm.py`` drives
it.

The closed loop, warm-up, window and comparison are ``moe_lm.Serve``'s;
what differs is the model: the program's ``ModelConfig`` (latent attention,
a leading dense layer, sigmoid routing over every routed expert of which
this chip holds its share, a shared expert), the weights and plain
reference of ``mla_moe_lm_ref.py``, and the work counts of
``work_mla.py``.  Each step is also counted as latent-attention work alone
(``mla_attn_units``, read by ``serve_mla_attn_roofline_pct``), and the
window's counts carry the program's ``moe.held_experts`` and
``mla.latent_cache_bytes`` counters.
"""

from __future__ import annotations

import gc
import time
from typing import Dict

import jax
import numpy as np

from bench import loadgen, seeds, work, work_mla
from bench.systems import mla_moe_lm_ref as ref
from bench.systems import moe_lm

__all__ = ["build", "model_config"]

TA = jax.profiler.TraceAnnotation


def build(config: Dict, traffic: Dict, seed: int, log):
    return Serve(config, traffic, seed, log)


def model_config(c: Dict):
    """The program's ``ModelConfig`` for the configuration file."""
    from repro.models.config import ModelConfig
    ys = c["rope_scaling"]
    return ModelConfig(
        name=c["name"], family="moe", n_layers=c["num_hidden_layers"],
        d_model=c["hidden_size"], n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"], d_ff=c["intermediate_size"],
        vocab=c["vocab_size"], dense_layers=c["first_k_dense_replace"],
        q_lora_rank=c["q_lora_rank"], kv_lora_rank=c["kv_lora_rank"],
        qk_nope_dim=c["qk_nope_head_dim"], qk_rope_dim=c["qk_rope_head_dim"],
        v_head_dim=c["v_head_dim"], rope_theta=float(c["rope_theta"]),
        yarn=(float(ys["factor"]), int(ys["original_max_position_embeddings"]),
              float(ys["beta_fast"]), float(ys["beta_slow"]),
              float(ys["mscale"]), float(ys["mscale_all_dim"])),
        norm_eps=c["rms_norm_eps"], moe_experts=c["n_routed_experts"],
        moe_topk=c["num_experts_per_tok"],
        moe_dff=c["moe_intermediate_size"],
        moe_shared_ff=c["moe_intermediate_size"] * c["n_shared_experts"],
        moe_score=c["scoring_func"],
        moe_score_bias=c["topk_method"] == "noaux_tc",
        moe_route_scale=float(c["routed_scaling_factor"]),
        moe_held=c["n_held_experts"], moe_held_offset=c["held_expert_offset"],
        moe_capacity=float(c["held_row_factor"]), dtype=c["torch_dtype"])


class Serve(moe_lm.Serve):
    def __init__(self, config: Dict, traffic: Dict, seed: int, log):
        self.config, self.traffic, self.log = config, traffic, log
        # a program without this model refuses its configuration at once
        self.model = model_config(config)
        self.mla = work_mla.shape_of(config)
        if traffic["arrival"]["kind"] != "closed":
            raise ValueError("this system serves closed-loop traffic only")
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
        self.engine = ServeEngine(self.model, self.params,
                                  batch=self.traffic["slots"],
                                  s_max=self.traffic["s_max"], greedy=True)
        self.stream = loadgen.RequestStream(self.traffic, seed,
                                            self.config["vocab_size"])
        self.rid = 0
        self.client_of, self.inflight, self.seen, self.all = {}, {}, {}, []
        t = time.perf_counter()
        self._warm()
        self.log(f"serving warm-up {time.perf_counter() - t:.6f} s "
                 f"({self.engine.steps} steps)")

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
                        n = req.prompt_len
                        account["units"].append(work_mla.prefill(self.mla, n))
                        account["attn"].append(
                            work_mla.attn_prefill(self.mla, n))
                        account["prefills"] += 1
                    if after - before > (before == 0):
                        ctxs.append(req.prompt_len + after - 1)
                if req.done:
                    del self.inflight[rid]
                    self._submit(self.client_of[rid])
            if account is not None and ctxs:
                account["units"].append(work_mla.decode_step(self.mla, ctxs))
                account["attn"].append(work_mla.attn_decode(self.mla, ctxs))
                account["decode_steps"] += 1

    def window(self, seconds: float) -> Dict:
        acc = {"tokens": 0, "prefills": 0, "decode_steps": 0, "units": [],
               "attn": []}
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
        units, attn = acc.pop("units"), acc.pop("attn")
        total = sum(units, work.Work(0.0, 0.0))
        from repro.core import sflog
        c = sflog.counters()
        return {"t0": t0, "t1": t1, "attempted": len(self.done_in_window),
                "tokens": acc["tokens"], "ttft_s": ttft, "serve_units": units,
                "mla_attn_units": attn,
                "counts": dict(acc, requests_finished=len(self.done_in_window),
                               first_tokens=len(ttft), flops=total.flops,
                               bytes=total.bytes,
                               held_experts=c.get("moe.held_experts"),
                               latent_cache_bytes=c.get(
                                   "mla.latent_cache_bytes"))}

    def readings(self, with_control: bool) -> Dict:
        sample = [(list(r.tokens), list(r.out)) for r in self._sample()]
        self.engine = self.params = None          # free the program's state
        gc.collect()
        t = time.perf_counter()
        params = ref.make_weights(self.config, seeds.jax_key(self.seed, 3))
        s_pad = self.traffic["prompt"]["max"] + self.traffic["output"]["max"]
        gaps, ctl_gaps, picks, served = [], [], [], 0
        for prompt, out in sample:
            seq = np.zeros(s_pad, np.int32)
            full = prompt + out[:-1]
            seq[:len(full)] = full
            pos = np.arange(len(prompt) - 1, len(full))
            logits, idx = ref.forward(params, seq, self.config, picks=True)
            want = np.asarray(logits)[pos]
            picks.append(np.asarray(idx)[:, pos[1:]])
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
        self._log_held_hits(picks)
        out = {"program": self._numbers(gaps), "control": None}
        if with_control:
            out["control"] = self._numbers(ctl_gaps)
        return out

    def _log_held_hits(self, picks) -> None:
        """Held experts a decode step reaches, against ``work_mla``'s
        uniform-routing count ``held (1 - (1 - 1/experts)^(slots topk))``:
        the decoded tokens of the checked requests, with the reference's
        picks in every MoE layer, dealt at random into steps of ``slots``
        tokens; each step and layer counts the distinct held experts its
        picks reach."""
        B, off, n = (self.traffic["slots"], self.config["held_expert_offset"],
                     self.config["n_held_experts"])
        p = np.concatenate(picks, axis=1) - off          # (layers, N, topk)
        steps = p.shape[1] // B
        if not steps or not p.shape[0]:
            return
        order = seeds.rng(self.seed, 7).permutation(p.shape[1])[:steps * B]
        p = p[:, order].reshape(p.shape[0], steps, -1)
        hit = [len(np.unique(r[(r >= 0) & (r < n)])) for l in p for r in l]
        want = self.mla.experts_hit(B * self.mla.topk)
        self.log(f"held experts hit a step: {np.mean(hit):.6f} over "
                 f"{len(hit)} steps x layers (uniform routing: {want:.6f})")
