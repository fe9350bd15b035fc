"""Weights from the seed, and the plain reference of the MoE decoder.

Imports nothing of the program.  ``make_weights`` builds the parameter tree
the served model reads, on the device in one jitted call.  ``forward`` is
the model's forward pass written out in float32 with ``highest`` matmul
precision, one sequence at a time, no cache and no batching:

* token embedding; per layer: RMSNorm, grouped-query attention with
  rotary embedding (half-split rotation, base ``rope_theta``) and a causal
  mask; residual; RMSNorm; router (softmax over the experts, the top-k
  picks renormalised to sum to 1); each token's output is the weighted sum
  of its top-k experts' SwiGLU (``silu(x W_gate) * (x W_in)) W_out``), with
  no capacity limit and no drops; residual;
* final RMSNorm and the output head.

``quant="fp8"`` is the control: every bf16 weight is rounded to float8
e4m3 with one scale per output channel (the weight-only quantisation a
faster path would be tempted by); the rest is unchanged.
"""

from __future__ import annotations

from functools import partial
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["make_weights", "forward", "LEAVES"]

# the leaves of the parameter tree, in the order their keys are folded
LEAVES = ("embed", "lm_head", "final_norm", "ln1", "ln2", "wq", "wk", "wv",
          "wo", "router", "w_in", "w_gate", "w_out")


def _shapes(c: Dict) -> Dict:
    L, D, V = c["num_hidden_layers"], c["hidden_size"], c["vocab_size"]
    H, K, hd = c["num_attention_heads"], c["num_key_value_heads"], \
        c["head_dim"]
    E, F = c["num_local_experts"], c["intermediate_size"]
    return {"embed": (V, D), "lm_head": (D, V), "final_norm": (D,),
            "ln1": (L, D), "ln2": (L, D), "wq": (L, D, H * hd),
            "wk": (L, D, K * hd), "wv": (L, D, K * hd), "wo": (L, H * hd, D),
            "router": (L, D, E), "w_in": (L, E, D, F),
            "w_gate": (L, E, D, F), "w_out": (L, E, F, D)}


def make_weights(config: Dict, key):
    """Random weights from ``key``: normal with the per-leaf ``init_std``
    of the configuration, norm scales 1; bf16 except the f32 router."""
    shapes = _shapes(config)
    std = config["init_std"]

    @jax.jit
    def make(key):
        leaves = {}
        for i, name in enumerate(LEAVES):
            shape = shapes[name]
            if name in ("final_norm", "ln1", "ln2"):
                leaves[name] = jnp.ones(shape, jnp.bfloat16)
                continue
            dt = jnp.float32 if name == "router" else jnp.bfloat16
            k = jax.random.fold_in(key, i)
            leaves[name] = (jax.random.normal(k, shape, jnp.float32)
                            * std[name]).astype(dt)
        blocks = {k: leaves[k] for k in LEAVES[3:]}
        return {"embed": leaves["embed"], "lm_head": leaves["lm_head"],
                "final_norm": leaves["final_norm"], "blocks": blocks}

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


def _rope(x, theta):
    S, _, hd = x.shape
    freqs = 1.0 / (theta ** (np.arange(0, hd, 2) / hd))
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * \
        jnp.asarray(freqs, jnp.float32)[None]
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


@partial(jax.jit, static_argnames=("dims", "quant"))
def _forward(params, tokens, *, dims, quant: Optional[str]):
    L, H, K, hd, E, topk, theta, eps = dims
    f32 = jnp.float32
    W = _fp8 if quant == "fp8" else (lambda w: w.astype(f32))
    b = params["blocks"]
    with jax.default_matmul_precision("highest"):
        x = jnp.take(W(params["embed"]), tokens, axis=0)      # (S, D)
        S = x.shape[0]
        causal = jnp.tril(jnp.ones((S, S), bool))
        for l in range(L):
            h = _rms(x, b["ln1"][l], eps)
            q = _rope((h @ W(b["wq"][l])).reshape(S, H, hd), theta)
            k = _rope((h @ W(b["wk"][l])).reshape(S, K, hd), theta)
            v = (h @ W(b["wv"][l])).reshape(S, K, hd)
            k, v = jnp.repeat(k, H // K, axis=1), jnp.repeat(v, H // K, axis=1)
            s = jnp.einsum("qhd,khd->hqk", q, k) / np.sqrt(hd)
            s = jnp.where(causal[None], s, -jnp.inf)
            o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1), v)
            x = x + o.reshape(S, H * hd) @ W(b["wo"][l])
            h = _rms(x, b["ln2"][l], eps)
            probs = jax.nn.softmax(h @ b["router"][l].astype(f32), -1)
            top, idx = jax.lax.top_k(probs, topk)
            gate = jnp.zeros((S, E), f32).at[
                jnp.arange(S)[:, None], idx].set(top / top.sum(-1, keepdims=True))
            ff = jnp.zeros_like(x)
            for e in range(E):
                act = jax.nn.silu(h @ W(b["w_gate"][l, e])) * \
                    (h @ W(b["w_in"][l, e]))
                ff = ff + gate[:, e:e + 1] * (act @ W(b["w_out"][l, e]))
            x = x + ff
        x = _rms(x, params["final_norm"], eps)
        return x @ W(params["lm_head"])


def forward(params, tokens, config: Dict, quant: Optional[str] = None):
    """Logits (S, V) in float32 at every position of ``tokens`` (S,)."""
    c = config
    dims = (c["num_hidden_layers"], c["num_attention_heads"],
            c["num_key_value_heads"], c["head_dim"], c["num_local_experts"],
            c["num_experts_per_tok"], float(c["rope_theta"]),
            float(c["rms_norm_eps"]))
    return _forward(params, jnp.asarray(tokens, jnp.int32), dims=dims,
                    quant=quant)
