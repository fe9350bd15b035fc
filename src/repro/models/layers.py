"""Core transformer layers: RMSNorm, RoPE, GQA attention (chunked online-
softmax for long context + KV-cache decode), SwiGLU/GELU MLPs.

Everything is a pure function over a params dict; layer params are stacked
along a leading L axis so the block stack runs under ``lax.scan`` (constant
compile time in depth — essential for the 61-88 layer dry-run configs).
"""

from __future__ import annotations

from functools import partial
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .config import ModelConfig
from .sharding import constrain
from ..core import sflog

__all__ = ["rmsnorm", "rope", "attention", "attention_decode",
           "attention_decode_slots", "mlp",
           "init_attn", "init_mlp", "cross_attention"]


def rmsnorm(x: jnp.ndarray, scale: jnp.ndarray, eps: float = 1e-5
            ) -> jnp.ndarray:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)
            ).astype(x.dtype)


def _rope_freqs(hd: int, theta: float) -> np.ndarray:
    return 1.0 / (theta ** (np.arange(0, hd, 2) / hd))


def rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float) -> jnp.ndarray:
    """x: (..., S, H, hd); positions: (S,) absolute positions."""
    hd = x.shape[-1]
    freqs = jnp.asarray(_rope_freqs(hd, theta), dtype=jnp.float32)
    ang = positions[:, None].astype(jnp.float32) * freqs[None, :]  # (S, hd/2)
    cos = jnp.cos(ang)[..., None, :]
    sin = jnp.sin(ang)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# --------------------------------------------------------------------------
# attention
# --------------------------------------------------------------------------
def init_attn(key, cfg: ModelConfig, layers: int) -> Dict:
    D, H, Hkv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd
    k1, k2, k3, k4 = jax.random.split(key, 4)
    s = 1.0 / np.sqrt(D)
    dt = jnp.dtype(cfg.dtype)
    p = {
        "wq": (jax.random.normal(k1, (layers, D, H * hd)) * s).astype(dt),
        "wk": (jax.random.normal(k2, (layers, D, Hkv * hd)) * s).astype(dt),
        "wv": (jax.random.normal(k3, (layers, D, Hkv * hd)) * s).astype(dt),
        "wo": (jax.random.normal(k4, (layers, H * hd, D))
               * (s / np.sqrt(2 * cfg.n_layers))).astype(dt),
    }
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((layers, hd), dt)
        p["k_norm"] = jnp.ones((layers, hd), dt)
    return p


def _chunked_attn(q, k, v, qpos0: int, causal: bool, window, chunk: int,
                  chunk_q: int = 512, scale: Optional[float] = None):
    """Flash-style attention as a checkpointed nested scan — the
    differentiable training/prefill counterpart of the Pallas flash kernel.

    Outer scan over Q chunks (each body under ``jax.checkpoint``: backward
    stores only per-q-chunk outputs, never the (Sq × Skv) logits); inner
    online-softmax scan over KV chunks.  q: (B, Sq, H, hd); k/v:
    (B, Skv, Hkv, hd); v may have another head dim than q and k (MLA);
    ``qpos0``: absolute position of q[0] (= Skv - Sq for suffix queries);
    ``scale`` defaults to 1/sqrt(hd).
    """
    B, Sq, H, hd = q.shape
    _, Skv, Hkv, _ = k.shape
    hdv = v.shape[-1]
    rep = H // Hkv
    if scale is None:
        scale = 1.0 / np.sqrt(hd)

    ck = min(chunk, Skv)
    nk = (Skv + ck - 1) // ck
    if nk * ck != Skv:
        k = jnp.pad(k, ((0, 0), (0, nk * ck - Skv), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, nk * ck - Skv), (0, 0), (0, 0)))
    kc = k.reshape(B, nk, ck, Hkv, hd).transpose(1, 0, 2, 3, 4)
    vc = v.reshape(B, nk, ck, Hkv, hdv).transpose(1, 0, 2, 3, 4)
    kv_off = jnp.arange(nk) * ck

    cq = min(chunk_q, Sq)
    nq = (Sq + cq - 1) // cq
    qf = q.astype(jnp.float32)
    if nq * cq != Sq:
        qf = jnp.pad(qf, ((0, 0), (0, nq * cq - Sq), (0, 0), (0, 0)))
    qc = qf.reshape(B, nq, cq, H, hd).transpose(1, 0, 2, 3, 4)
    q_off = jnp.arange(nq) * cq

    @partial(jax.checkpoint, prevent_cse=False)
    def q_chunk_body(_, inp):
        qb, q0 = inp                           # (B, cq, H, hd), offset
        qpos = qpos0 + q0 + jnp.arange(cq)

        qg = qb.reshape(B, cq, Hkv, rep, hd)

        def kv_body(carry, kv_in):
            m, l, acc = carry                   # (B, Hkv, rep, cq[, hd])
            kb, vb, c0 = kv_in                  # (B, ck, Hkv, hd)
            s = jnp.einsum("bqkrd,bckd->bkrqc", qg, kb.astype(jnp.float32)
                           ) * scale
            kpos = c0 + jnp.arange(ck)
            mask = kpos[None, :] < Skv
            if causal:
                mask = mask & (kpos[None, :] <= qpos[:, None])
            if window is not None:
                mask = mask & (kpos[None, :] > qpos[:, None] - window)
            s = jnp.where(mask[None, None, None], s, -1e30)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + jnp.sum(p, axis=-1)
            acc_new = acc * corr[..., None] + \
                jnp.einsum("bkrqc,bckd->bkrqd", p, vb.astype(jnp.float32))
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((B, Hkv, rep, cq), -1e30, jnp.float32)
        l0 = jnp.zeros((B, Hkv, rep, cq), jnp.float32)
        a0 = jnp.zeros((B, Hkv, rep, cq, hdv), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(kv_body, (m0, l0, a0),
                                      (kc, vc, kv_off))
        l = jnp.where(l == 0.0, 1.0, l)
        out_g = (acc / l[..., None]).astype(q.dtype)     # (B,Hkv,rep,cq,hd)
        return None, out_g.reshape(B, Hkv * rep, cq, hdv)

    _, outs = jax.lax.scan(q_chunk_body, None, (qc, q_off))
    # outs: (nq, B, H, cq, hdv) -> (B, Sq, H, hdv)
    out = outs.transpose(1, 0, 3, 2, 4).reshape(B, nq * cq, H, hdv)
    return out[:, :Sq]


def attention(x: jnp.ndarray, p: Dict, cfg: ModelConfig, *,
              positions: Optional[jnp.ndarray] = None,
              causal: bool = True, window=None, chunk: int = 1024,
              kv_override: Optional[Tuple[jnp.ndarray, jnp.ndarray]] = None,
              ) -> Tuple[jnp.ndarray, Tuple[jnp.ndarray, jnp.ndarray]]:
    """Full-sequence attention (training / prefill).

    Returns (output, (k, v)) so prefill can seed the KV cache.
    ``kv_override`` feeds encoder K/V for cross-attention.
    """
    with sflog.scope("model.attn"):
        B, S, D = x.shape
        H, Hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
        q = constrain((x @ p["wq"]).reshape(B, S, H, hd), model_dim=2)
        if kv_override is None:
            k = constrain((x @ p["wk"]).reshape(B, S, Hkv, hd),
                          model_dim=2)
            v = constrain((x @ p["wv"]).reshape(B, S, Hkv, hd),
                          model_dim=2)
        else:
            k, v = kv_override
        if cfg.qk_norm:
            q = rmsnorm(q, p["q_norm"], cfg.norm_eps)
            if kv_override is None:
                k = rmsnorm(k, p["k_norm"], cfg.norm_eps)
        if positions is None:
            positions = jnp.arange(S)
        if kv_override is None:
            q = rope(q, positions, cfg.rope_theta)
            k = rope(k, positions, cfg.rope_theta)
        Skv = k.shape[1]
        out = _chunked_attn(q, k, v,
                            qpos0=Skv - S if kv_override is None else 0,
                            causal=causal, window=window,
                            chunk=min(chunk, Skv))
        out = constrain(out, model_dim=2)
        return constrain(out.reshape(B, S, H * hd) @ p["wo"]), (k, v)


def cross_attention(x, p, cfg: ModelConfig, enc_kv):
    out, _ = attention(x, p, cfg, causal=False, kv_override=enc_kv)
    return out


def attention_decode(x: jnp.ndarray, p: Dict, cfg: ModelConfig, cache_k,
                     cache_v, pos: jnp.ndarray, *, window=None,
                     chunk: int = 2048):
    """Single-token decode: x (B, 1, D); cache_k/v (B, Smax, Hkv, hd);
    pos: () current absolute position.  Returns (out, cache_k', cache_v')."""
    with sflog.scope("model.attn"):
        B, _, D = x.shape
        H, Hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
        q = (x @ p["wq"]).reshape(B, 1, H, hd)
        k = (x @ p["wk"]).reshape(B, 1, Hkv, hd)
        v = (x @ p["wv"]).reshape(B, 1, Hkv, hd)
        if cfg.qk_norm:
            q = rmsnorm(q, p["q_norm"], cfg.norm_eps)
            k = rmsnorm(k, p["k_norm"], cfg.norm_eps)
        q = rope(q, pos[None], cfg.rope_theta)
        k = rope(k, pos[None], cfg.rope_theta)
        at = (0, pos.astype(jnp.int32), 0, 0)
        cache_k = jax.lax.dynamic_update_slice(
            cache_k, k.astype(cache_k.dtype), at)
        cache_v = jax.lax.dynamic_update_slice(
            cache_v, v.astype(cache_v.dtype), at)
        Smax = cache_k.shape[1]
        rep = H // Hkv
        scale = 1.0 / np.sqrt(hd)
        # grouped-query attention WITHOUT materializing the repeated (or
        # fp32) cache: q regrouped to (B, Hkv, rep, hd), contractions in fp32
        # via preferred_element_type (memory term stays 2 bytes/cache
        # element)
        qg = q.reshape(B, Hkv, rep, hd)
        s = jnp.einsum("bkrd,bskd->bkrs", qg, cache_k,
                       preferred_element_type=jnp.float32) * scale
        kpos = jnp.arange(Smax)
        mask = kpos <= pos
        if window is not None:
            mask &= kpos > pos - window
        s = jnp.where(mask[None, None, None, :], s, -1e30)
        pr = jax.nn.softmax(s, axis=-1)
        out = jnp.einsum("bkrs,bskd->bkrd", pr.astype(cache_v.dtype), cache_v,
                         preferred_element_type=jnp.float32).astype(x.dtype)
        return out.reshape(B, 1, H * hd) @ p["wo"], cache_k, cache_v


def attention_decode_slots(x: jnp.ndarray, p: Dict, cfg: ModelConfig,
                           ck, cv, pos: jnp.ndarray):
    """Single-token decode in which each batch row has its own position:
    x (B, 1, D); ck/cv (B, Smax, Hkv, hd); pos (B,).  The serving engine's
    slot form (K/V repeated to the query heads in f32).  Returns
    (out (B, 1, D), ck', cv')."""
    with sflog.scope("model.attn"):
        B = x.shape[0]
        H, Hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
        q = (x @ p["wq"]).reshape(B, 1, H, hd)
        k = (x @ p["wk"]).reshape(B, 1, Hkv, hd)
        v = (x @ p["wv"]).reshape(B, 1, Hkv, hd)
        if cfg.qk_norm:
            q = rmsnorm(q, p["q_norm"], cfg.norm_eps)
            k = rmsnorm(k, p["k_norm"], cfg.norm_eps)
        # per-row rope + cache write
        def rope1(u, p_):
            # u: (H, hd), p_: scalar -> rope at one absolute position
            return rope(u[None], p_[None], cfg.rope_theta)[0]
        q = jax.vmap(rope1)(q[:, 0], pos)[:, None]  # (B, 1, H, hd)
        k = jax.vmap(rope1)(k[:, 0], pos)[:, None]
        ck = jax.vmap(
            lambda c, kk, p_: jax.lax.dynamic_update_slice(
                c, kk.astype(c.dtype), (p_, 0, 0)))(
                    ck, k[:, 0][:, None], pos)
        cv = jax.vmap(
            lambda c, vv, p_: jax.lax.dynamic_update_slice(
                c, vv.astype(c.dtype), (p_, 0, 0)))(
                    cv, v[:, 0][:, None], pos)
        rep = H // Hkv
        scale = 1.0 / np.sqrt(hd)
        kf = jnp.repeat(ck.astype(jnp.float32), rep, axis=2)
        vf = jnp.repeat(cv.astype(jnp.float32), rep, axis=2)
        s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                       kf) * scale
        kpos = jnp.arange(ck.shape[1])
        mask = kpos[None] <= pos[:, None]
        if cfg.attn_window:
            mask &= kpos[None] > pos[:, None] - cfg.attn_window
        s = jnp.where(mask[:, None, None, :], s, -1e30)
        pr = jax.nn.softmax(s, axis=-1)
        attn = jnp.einsum("bhqk,bkhd->bqhd", pr, vf).astype(x.dtype)
        return attn.reshape(B, 1, H * hd) @ p["wo"], ck, cv


# --------------------------------------------------------------------------
# feed-forward
# --------------------------------------------------------------------------
def init_mlp(key, cfg: ModelConfig, layers: int, d_ff: Optional[int] = None
             ) -> Dict:
    D = cfg.d_model
    F = d_ff if d_ff is not None else cfg.d_ff
    dt = jnp.dtype(cfg.dtype)
    s = 1.0 / np.sqrt(D)
    so = 1.0 / np.sqrt(F) / np.sqrt(2 * cfg.n_layers)
    k1, k2, k3 = jax.random.split(key, 3)
    p = {
        "w_in": (jax.random.normal(k1, (layers, D, F)) * s).astype(dt),
        "w_out": (jax.random.normal(k2, (layers, F, D)) * so).astype(dt),
    }
    if cfg.mlp_kind == "swiglu":
        p["w_gate"] = (jax.random.normal(k3, (layers, D, F)) * s).astype(dt)
    return p


def mlp(x: jnp.ndarray, p: Dict, cfg: ModelConfig) -> jnp.ndarray:
    h = constrain(x @ p["w_in"], model_dim=2)
    if cfg.mlp_kind == "swiglu":
        h = jax.nn.silu(constrain(x @ p["w_gate"], model_dim=2)) * h
    else:
        h = jax.nn.gelu(h)
    return constrain(h @ p["w_out"])
