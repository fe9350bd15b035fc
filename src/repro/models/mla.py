"""Multi-head latent attention (MLA, DeepSeek-V2/V3 and Kimi-K2).

Per token (D the model width, H heads):

* ``c_q = RMSNorm(x W_qa)`` (``q_lora_rank``); ``q = c_q W_qb`` gives each
  head ``[q_nope | q_rope]`` and ``q_rope`` gets RoPE;
* ``a = x W_kva``; ``c_kv = RMSNorm(a[:kv_lora_rank])``; ``k_rope =
  RoPE(a[kv_lora_rank:])`` is one key shared by every head;
* ``c_kv W_kvb`` gives each head ``[k_nope | v]``;
* ``score_h = (q_nope . k_nope + q_rope . k_rope) * scale`` with ``scale =
  m^2 / sqrt(nope + rope)`` (``m`` the YaRN ``mscale_all_dim`` factor), and
  ``o = concat_h softmax(score_h) v_h``, then ``o W_o``.

The cache holds ``c_kv`` and ``k_rope`` per token (the latent cache).  Full
sequences expand K/V per head and run online-softmax attention in blocks:
serving's prefill in the Pallas flash kernel, training in the
differentiable chunked scan; decode absorbs ``W_kvb`` into the query and the
output (``q_lat = q_nope W_kvb_k^T``, ``o_h = (sum p c_kv) W_kvb_v``) and
reads only the latent cache.

RoPE follows the published DeepSeek-V3 modelling code: each rotary input is
de-interleaved (even then odd components) before the half-split rotation,
with YaRN's blended inverse frequencies.
"""

from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .config import ModelConfig
from .layers import _chunked_attn, rmsnorm
from ..core import sflog
from ..kernels.flash_attention import flash_attention_heads

__all__ = ["init_mla", "mla_attention", "mla_decode", "mla_rope_freqs",
           "mla_scale"]


def _yarn_mscale(scale: float, mscale: float) -> float:
    return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0


def mla_rope_freqs(cfg: ModelConfig) -> Tuple[np.ndarray, float]:
    """(inverse frequencies (rope_dim / 2,), cos/sin scale) of the rotary
    part, YaRN-blended when ``cfg.yarn`` is set."""
    dim, base = cfg.qk_rope_dim, cfg.rope_theta
    extra = 1.0 / (base ** (np.arange(0, dim, 2, dtype=np.float64) / dim))
    if not cfg.yarn:
        return extra.astype(np.float32), 1.0
    factor, orig, beta_fast, beta_slow, mscale, mscale_all = cfg.yarn

    def corr_dim(rot):
        return dim * math.log(orig / (rot * 2 * math.pi)) / (2 * math.log(base))

    low = max(math.floor(corr_dim(beta_fast)), 0)
    high = min(math.ceil(corr_dim(beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    keep = 1.0 - ramp                     # 1: extrapolate, 0: interpolate
    inv = extra / factor * (1 - keep) + extra * keep
    m = _yarn_mscale(factor, mscale) / _yarn_mscale(factor, mscale_all)
    return inv.astype(np.float32), float(m)


def mla_scale(cfg: ModelConfig) -> float:
    """The softmax scale: 1/sqrt(nope + rope), times m^2 under YaRN."""
    s = 1.0 / math.sqrt(cfg.qk_nope_dim + cfg.qk_rope_dim)
    if cfg.yarn and cfg.yarn[5]:
        s *= _yarn_mscale(cfg.yarn[0], cfg.yarn[5]) ** 2
    return s


def _rope(x, positions, cfg: ModelConfig, heads: bool):
    """RoPE of the rotary components ``x`` (P, [H,] rope_dim) or (B, P,
    [H,] rope_dim) at ``positions`` (P,); ``heads`` says whether a heads
    axis follows the positions axis.  Pairs are interleaved (the published
    code de-interleaves, then rotates half against half)."""
    inv, m = mla_rope_freqs(cfg)
    ang = positions.astype(jnp.float32)[:, None] * jnp.asarray(inv)[None]
    cos, sin = jnp.cos(ang) * m, jnp.sin(ang) * m          # (P, dim/2)
    if heads:
        cos, sin = cos[:, None], sin[:, None]
    xf = x.astype(jnp.float32)
    x1, x2 = xf[..., 0::2], xf[..., 1::2]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
    return out.astype(x.dtype)


def init_mla(key, cfg: ModelConfig, layers: int) -> Dict:
    D, H = cfg.d_model, cfg.n_heads
    qr, kr, dr = cfg.q_lora_rank, cfg.kv_lora_rank, cfg.qk_rope_dim
    dq, dv = cfg.qk_nope_dim + dr, cfg.v_head_dim
    dt = jnp.dtype(cfg.dtype)
    ks = jax.random.split(key, 5)

    def w(k, shape, fan_in, out_scale=1.0):
        return (jax.random.normal(k, (layers,) + shape)
                * (out_scale / np.sqrt(fan_in))).astype(dt)

    return {
        "wq_a": w(ks[0], (D, qr), D),
        "q_norm": jnp.ones((layers, qr), dt),
        "wq_b": w(ks[1], (qr, H * dq), qr),
        "wkv_a": w(ks[2], (D, kr + dr), D),
        "kv_norm": jnp.ones((layers, kr), dt),
        "wkv_b": w(ks[3], (kr, H * (cfg.qk_nope_dim + dv)), kr),
        "wo": w(ks[4], (H * dv, D), H * dv, 1.0 / np.sqrt(2 * cfg.n_layers)),
    }


def _q(x, p, cfg: ModelConfig, positions):
    """Per-head queries ``(q_nope, q_rope)``, the rotary part roped."""
    with sflog.scope("mla.q"):
        H, dn = cfg.n_heads, cfg.qk_nope_dim
        cq = rmsnorm(x @ p["wq_a"], p["q_norm"], cfg.norm_eps)
        q = (cq @ p["wq_b"]).reshape(x.shape[:-1] + (H, dn + cfg.qk_rope_dim))
        return q[..., :dn], _rope(q[..., dn:], positions, cfg, heads=True)


def _latent(x, p, cfg: ModelConfig, positions):
    """The latent cache rows of ``x``: ``(c_kv, k_rope)``."""
    with sflog.scope("mla.kv"):
        kr = cfg.kv_lora_rank
        a = x @ p["wkv_a"]
        ckv = rmsnorm(a[..., :kr], p["kv_norm"], cfg.norm_eps)
        return ckv, _rope(a[..., kr:], positions, cfg, heads=False)


def mla_attention(x: jnp.ndarray, p: Dict, cfg: ModelConfig, *,
                  chunk: int = 1024, kernel: bool = False,
                  lengths: Optional[jnp.ndarray] = None
                  ) -> Tuple[jnp.ndarray, Dict]:
    """Causal MLA over a full sequence x (B, S, D), K/V expanded per head.
    Returns (output (B, S, D), {"ckv": (B, S, kv_lora_rank), "kpe": (B, S,
    rope_dim)}) so prefill can seed the latent cache.  ``kernel`` runs the
    attention in the Pallas flash kernel (forward only, head-major, causal
    blocks skipped: serving's prefill), which skips the rows of each
    sequence past its ``lengths`` (B,) (a bucketed prompt's pad tail, whose
    outputs are then zero); otherwise the differentiable chunked scan
    (training)."""
    with sflog.scope("model.attn"):
        B, S, _ = x.shape
        H, dn, dv = cfg.n_heads, cfg.qk_nope_dim, cfg.v_head_dim
        positions = jnp.arange(S)
        q_nope, q_pe = _q(x, p, cfg, positions)
        ckv, kpe = _latent(x, p, cfg, positions)
        with sflog.scope("mla.attn"):
            kv = (ckv @ p["wkv_b"]).reshape(B, S, H, dn + dv)
            q = jnp.concatenate([q_nope, q_pe], -1)
            k = jnp.concatenate(
                [kv[..., :dn], jnp.broadcast_to(kpe[:, :, None],
                                                (B, S, H, cfg.qk_rope_dim))],
                -1)
            v = kv[..., dn:]
            if kernel:
                heads = lambda a: a.transpose(0, 2, 1, 3).reshape(
                    (B * H, S, a.shape[-1]))
                n = None if lengths is None else jnp.repeat(
                    jnp.asarray(lengths, jnp.int32), H)
                out = flash_attention_heads(
                    heads(q), heads(k), heads(v), n, scale=mla_scale(cfg))
                out = out.reshape(B, H, S, dv).transpose(0, 2, 1, 3)
            else:
                out = _chunked_attn(q, k, v, qpos0=0, causal=True,
                                    window=None, chunk=min(chunk, S),
                                    scale=mla_scale(cfg))
        return out.reshape(B, S, H * dv) @ p["wo"], {"ckv": ckv, "kpe": kpe}


def mla_decode(x: jnp.ndarray, p: Dict, cfg: ModelConfig, ckv, kpe,
               positions: jnp.ndarray):
    """One token per row: x (B, 1, D) at ``positions`` (B,); ``ckv`` (B,
    Smax, kv_lora_rank) and ``kpe`` (B, Smax, rope_dim) are the row's
    latent cache.  Absorbed form: the cache is never expanded per head.
    Returns (output (B, 1, D), ckv', kpe')."""
    with sflog.scope("model.attn"):
        B = x.shape[0]
        H, dn, dv, kr = cfg.n_heads, cfg.qk_nope_dim, cfg.v_head_dim, \
            cfg.kv_lora_rank
        q_nope, q_pe = _q(x[:, 0], p, cfg, positions)     # (B, H, .)
        c_new, k_new = _latent(x[:, 0], p, cfg, positions)  # (B, .)
        write = jax.vmap(lambda c, row, at: jax.lax.dynamic_update_slice(
            c, row[None].astype(c.dtype), (at, 0)))
        ckv = write(ckv, c_new, positions)
        kpe = write(kpe, k_new, positions)
        with sflog.scope("mla.attn"):
            wkv_b = p["wkv_b"].reshape(kr, H, dn + dv)
            f32 = jnp.float32
            q_lat = jnp.einsum("bhn,rhn->bhr", q_nope, wkv_b[..., :dn],
                               preferred_element_type=f32).astype(ckv.dtype)
            s = jnp.einsum("bhr,bsr->bhs", q_lat, ckv,
                           preferred_element_type=f32)
            s = s + jnp.einsum("bhd,bsd->bhs", q_pe.astype(kpe.dtype), kpe,
                               preferred_element_type=f32)
            s = s * mla_scale(cfg)
            kpos = jnp.arange(ckv.shape[1])
            s = jnp.where((kpos[None] <= positions[:, None])[:, None], s,
                          -1e30)
            pr = jax.nn.softmax(s, axis=-1).astype(ckv.dtype)
            o_lat = jnp.einsum("bhs,bsr->bhr", pr, ckv,
                               preferred_element_type=f32)
            o = jnp.einsum("bhr,rhv->bhv", o_lat.astype(x.dtype),
                           wkv_b[..., dn:], preferred_element_type=f32)
        out = o.astype(x.dtype).reshape(B, 1, H * dv) @ p["wo"]
        return out, ckv, kpe
