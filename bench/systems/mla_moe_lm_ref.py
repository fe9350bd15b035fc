"""Weights from the seed, and the plain reference of the latent-attention
MoE decoder (the DeepSeek-V3 block of Kimi-K2).

Imports nothing of the program.  ``make_weights`` builds the parameter tree
the served model reads, on the device in one jitted call.  ``forward`` is
the model's forward pass written out in float32 with ``highest`` matmul
precision, one sequence at a time, no cache and no batching:

* token embedding; ``first_k_dense_replace`` dense layers, then the MoE
  layers; each layer: RMSNorm, latent attention, residual, RMSNorm, FFN,
  residual;
* latent attention in the expanded form: ``c_q = RMSNorm(x W_qa)``, ``q =
  c_q W_qb`` per head ``[q_nope | q_rope]``; ``a = x W_kva``, ``c_kv =
  RMSNorm(a[:kv_lora_rank])``, ``k_rope = a[kv_lora_rank:]``; ``c_kv W_kvb``
  per head ``[k_nope | v]``; RoPE as the published modelling code applies
  it (the rotary input viewed as pairs, de-interleaved, then rotated half
  against half) with YaRN's blended frequencies; scores ``(q . k) * m^2 /
  sqrt(nope + rope)``, causal softmax, computed in blocks of queries so a
  13k-token sequence fits the chip;
* the dense FFN: SwiGLU ``(silu(x W_gate) * (x W_in)) W_out``;
* the MoE FFN: sigmoid scores of the f32 router over every routed expert,
  top-k chosen on scores plus the correction bias, the chosen scores
  normalised and scaled by ``routed_scaling_factor``; each expert held here
  (``n_held_experts`` from ``held_expert_offset``) computed densely for
  every token and weighted by its gate (zero where not chosen), plus the
  shared expert; what the absent experts would add lies on other chips and
  is left out, as in the program;
* final RMSNorm and the output head over the vocabulary slice.

``quant="fp8"`` is the control: every bf16 weight is rounded to float8
e4m3 with one scale per output channel; the rest is unchanged.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Dict, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["make_weights", "forward", "route", "moe_ffn", "dims", "Dims"]

NORMS = ("ln1", "ln2", "q_norm", "kv_norm", "final_norm")
QUERY_BLOCK = 512


class Dims(NamedTuple):
    dense: int
    moe: int
    heads: int
    q_rank: int
    kv_rank: int
    nope: int
    rope: int
    v: int
    experts: int
    topk: int
    held: int
    offset: int
    route_scale: float
    theta: float
    yarn: tuple
    eps: float


def dims(c: Dict) -> Dims:
    ys = c.get("rope_scaling") or {}
    yarn = (float(ys["factor"]), int(ys["original_max_position_embeddings"]),
            float(ys["beta_fast"]), float(ys["beta_slow"]),
            float(ys["mscale"]), float(ys["mscale_all_dim"])) if ys else ()
    Ld = c["first_k_dense_replace"]
    return Dims(Ld, c["num_hidden_layers"] - Ld, c["num_attention_heads"],
                c["q_lora_rank"], c["kv_lora_rank"], c["qk_nope_head_dim"],
                c["qk_rope_head_dim"], c["v_head_dim"],
                c["n_routed_experts"], c["num_experts_per_tok"],
                c["n_held_experts"], c["held_expert_offset"],
                float(c["routed_scaling_factor"]), float(c["rope_theta"]),
                yarn, float(c["rms_norm_eps"]))


def _shapes(c: Dict) -> Dict:
    d = dims(c)
    D, V = c["hidden_size"], c["vocab_size"]
    H, dq = d.heads, d.nope + d.rope
    F, Fd = c["moe_intermediate_size"], c["intermediate_size"]
    Fs = F * c["n_shared_experts"]

    def attn(L):
        return {"ln1": (L, D), "ln2": (L, D), "wq_a": (L, D, d.q_rank),
                "q_norm": (L, d.q_rank), "wq_b": (L, d.q_rank, H * dq),
                "wkv_a": (L, D, d.kv_rank + d.rope),
                "kv_norm": (L, d.kv_rank),
                "wkv_b": (L, d.kv_rank, H * (d.nope + d.v)),
                "wo": (L, H * d.v, D)}

    dense = dict(attn(d.dense), w_in=(d.dense, D, Fd),
                 w_gate=(d.dense, D, Fd), w_out=(d.dense, Fd, D))
    L, n = d.moe, d.held
    moe = dict(attn(L), router=(L, D, d.experts), router_bias=(L, d.experts),
               w_in=(L, n, D, F), w_gate=(L, n, D, F), w_out=(L, n, F, D),
               shared_in=(L, D, Fs), shared_gate=(L, D, Fs),
               shared_out=(L, Fs, D))
    return {"embed": (V, D), "lm_head": (D, V), "final_norm": (D,),
            "dense_blocks": dense, "blocks": moe}


def make_weights(config: Dict, key):
    """Random weights from ``key``: normal with the per-leaf ``init_std``
    of the configuration (the expert leaves share ``expert_*``, the dense
    FFN ``dense_*``), norm scales 1, the correction bias uniform in
    ``[0, router_bias_max)``; bf16 except the f32 router and bias."""
    shapes = _shapes(config)
    std = config["init_std"]

    def leaf(name, group, shape, k):
        if name in NORMS:
            return jnp.ones(shape, jnp.bfloat16)
        if name == "router_bias":
            return jax.random.uniform(k, shape, jnp.float32, 0.0,
                                      config["router_bias_max"])
        sname = name
        if group == "dense_blocks" and name.startswith("w_"):
            sname = "dense_" + name
        elif group == "blocks" and name.startswith("w_"):
            sname = "expert_" + name
        dt = jnp.float32 if name == "router" else jnp.bfloat16
        return (jax.random.normal(k, shape, jnp.float32)
                * std[sname]).astype(dt)

    @jax.jit
    def make(key):
        out, i = {}, 0
        for group, spec in shapes.items():
            if isinstance(spec, dict):
                out[group] = {}
                for name, shape in spec.items():
                    out[group][name] = leaf(name, group, shape,
                                            jax.random.fold_in(key, i))
                    i += 1
            else:
                out[group] = leaf(group, None, spec,
                                  jax.random.fold_in(key, i))
                i += 1
        return out

    return make(key)


def _fp8(w):
    """Round to float8 e4m3 with one scale per output channel (last axis)."""
    w = w.astype(jnp.float32)
    axes = tuple(range(w.ndim - 1))
    scale = jnp.max(jnp.abs(w), axis=axes, keepdims=True) / 448.0
    scale = jnp.where(scale > 0, scale, 1.0)
    return (w / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale.astype(jnp.float32)


def _mscale(scale, m):
    return 1.0 if scale <= 1 else 0.1 * m * math.log(scale) + 1.0


def _cos_sin(S: int, d: Dims):
    """cos, sin (S, rope) as the published YaRN rotary embedding builds
    them (frequencies repeated for the two halves)."""
    dim, base = d.rope, d.theta
    freq_extra = 1.0 / base ** (np.arange(0, dim, 2) / dim)
    if not d.yarn:
        inv, m = freq_extra, 1.0
    else:
        factor, orig, fast, slow, ms, ms_all = d.yarn
        freq_inter = 1.0 / (factor * base ** (np.arange(0, dim, 2) / dim))

        def corr(rot):
            return dim * math.log(orig / (rot * 2 * math.pi)) \
                / (2 * math.log(base))

        lo = max(math.floor(corr(fast)), 0)
        hi = min(math.ceil(corr(slow)), dim - 1)
        if lo == hi:
            hi += 0.001
        ramp = np.clip((np.arange(dim // 2) - lo) / (hi - lo), 0, 1)
        mask = 1.0 - ramp
        inv = freq_inter * (1 - mask) + freq_extra * mask
        m = _mscale(factor, ms) / _mscale(factor, ms_all)
    freqs = np.outer(np.arange(S), inv)
    emb = np.concatenate([freqs, freqs], -1)
    return (jnp.asarray(np.cos(emb) * m, jnp.float32),
            jnp.asarray(np.sin(emb) * m, jnp.float32))


def _apply_rope(x, cos, sin):
    """x (S, ..., rope): view as (rope/2, 2) pairs, transpose, then
    ``x * cos + rotate_half(x) * sin`` (DeepSeek-V3 ``apply_rotary_pos_emb``)."""
    S, r = x.shape[0], x.shape[-1]
    mid = x.shape[1:-1]
    x = x.reshape((S,) + mid + (r // 2, 2))
    x = jnp.swapaxes(x, -1, -2).reshape((S,) + mid + (r,))
    c = cos.reshape((S,) + (1,) * len(mid) + (r,))
    s = sin.reshape((S,) + (1,) * len(mid) + (r,))
    x1, x2 = x[..., : r // 2], x[..., r // 2:]
    rot = jnp.concatenate([-x2, x1], -1)
    return x * c + rot * s


def _attention(x, lp, d: Dims, cos, sin, W):
    S = x.shape[0]
    H, dq = d.heads, d.nope + d.rope
    cq = _rms(x @ W(lp["wq_a"]), lp["q_norm"], d.eps)
    q = (cq @ W(lp["wq_b"])).reshape(S, H, dq)
    q = jnp.concatenate([q[..., :d.nope],
                         _apply_rope(q[..., d.nope:], cos, sin)], -1)
    a = x @ W(lp["wkv_a"])
    ckv = _rms(a[:, :d.kv_rank], lp["kv_norm"], d.eps)
    kpe = _apply_rope(a[:, d.kv_rank:], cos, sin)
    kv = (ckv @ W(lp["wkv_b"])).reshape(S, H, d.nope + d.v)
    k = jnp.concatenate([kv[..., :d.nope],
                         jnp.broadcast_to(kpe[:, None], (S, H, d.rope))], -1)
    v = kv[..., d.nope:]
    scale = 1.0 / math.sqrt(dq)
    if d.yarn and d.yarn[5]:
        scale *= _mscale(d.yarn[0], d.yarn[5]) ** 2
    nb = S // QUERY_BLOCK if S % QUERY_BLOCK == 0 and S > QUERY_BLOCK else 1
    qb = S // nb

    def block(i):
        qi = jax.lax.dynamic_slice_in_dim(q, i * qb, qb)      # (qb, H, dq)
        s = jnp.einsum("qhd,khd->hqk", qi, k) * scale
        keep = jnp.arange(S)[None] <= (i * qb + jnp.arange(qb))[:, None]
        s = jnp.where(keep[None], s, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1), v)

    o = jax.lax.map(block, jnp.arange(nb)).reshape(S, H * d.v)
    return o @ W(lp["wo"])


def _swiglu(h, w_in, w_gate, w_out):
    return (jax.nn.silu(h @ w_gate) * (h @ w_in)) @ w_out


def route(h, lp, d: Dims):
    """(expert ids (S, topk), their weights): sigmoid scores of the f32
    router, top-k of scores plus the correction bias, the chosen scores
    normalised and scaled."""
    scores = jax.nn.sigmoid(h @ lp["router"].astype(jnp.float32))
    _, idx = jax.lax.top_k(scores + lp["router_bias"], d.topk)
    top = jnp.take_along_axis(scores, idx, -1)
    return idx, top / (top.sum(-1, keepdims=True) + 1e-20) * d.route_scale


def moe_ffn(h, lp, d: Dims, W=lambda w: w.astype(jnp.float32)):
    """The MoE FFN of one layer for h (S, D): the held experts' part of the
    routed sum, plus the shared expert."""
    S = h.shape[0]
    idx, top = route(h, lp, d)
    gate = jnp.zeros((S, d.experts), jnp.float32).at[
        jnp.arange(S)[:, None], idx].set(top)
    ff = _swiglu(h, W(lp["shared_in"]), W(lp["shared_gate"]),
                 W(lp["shared_out"]))
    for e in range(d.held):
        ff = ff + gate[:, d.offset + e:d.offset + e + 1] * _swiglu(
            h, W(lp["w_in"][e]), W(lp["w_gate"][e]), W(lp["w_out"][e]))
    return ff


@partial(jax.jit, static_argnames=("d", "quant"))
def _forward(params, tokens, *, d: Dims, quant: Optional[str]):
    f32 = jnp.float32
    W = _fp8 if quant == "fp8" else (lambda w: w.astype(f32))
    with jax.default_matmul_precision("highest"):
        x = jnp.take(W(params["embed"]), tokens, axis=0)       # (S, D)
        cos, sin = _cos_sin(x.shape[0], d)
        picks = []
        for group, n in (("dense_blocks", d.dense), ("blocks", d.moe)):
            b = params[group]
            for l in range(n):
                lp = jax.tree.map(lambda a: a[l], b)
                x = x + _attention(_rms(x, lp["ln1"], d.eps), lp, d, cos,
                                   sin, W)
                h = _rms(x, lp["ln2"], d.eps)
                if group == "dense_blocks":
                    x = x + _swiglu(h, W(lp["w_in"]), W(lp["w_gate"]),
                                    W(lp["w_out"]))
                else:
                    x = x + moe_ffn(h, lp, d, W)
                    picks.append(route(h, lp, d)[0])
        x = _rms(x, params["final_norm"], d.eps)
        idx = jnp.stack(picks) if picks else jnp.zeros(
            (0, x.shape[0], d.topk), jnp.int32)
        return x @ W(params["lm_head"]), idx


def forward(params, tokens, config: Dict, quant: Optional[str] = None,
            picks: bool = False):
    """Logits (S, V) in float32 at every position of ``tokens`` (S,); with
    ``picks`` also the expert ids each MoE layer routes each position to,
    (MoE layers, S, topk)."""
    logits, idx = _forward(params, jnp.asarray(tokens, jnp.int32),
                           d=dims(config), quant=quant)
    return (logits, idx) if picks else logits
