"""Top-level model definitions for all assigned architecture families.

One functional namespace drives every family through the config:

  init_params     parameters with layer-stacked (L, ...) leaves
  forward         training forward -> (logits, aux) — scan over layers
  prefill         full-sequence forward -> (last logits, decode caches)
  decode_step     single-token step on the caches

Families:
  dense / vlm         pre-norm GQA transformer (vlm consumes precomputed
                      patch+token embeddings — frontend stubbed per brief)
  moe                 same skeleton, FFN -> MoE layer (EP)
  hybrid (hymba)      parallel attention + SSM heads per block; per-layer
                      sliding-window/global attention schedule
  ssm (xlstm)         (mLSTM, sLSTM) pair blocks, no attention
  audio (whisper)     encoder-decoder; encoder eats precomputed mel-frame
                      embeddings (stub), decoder has cross-attention

Layer stacking + ``lax.scan`` keeps compile time flat in depth (88-layer
mistral-large compiles the same HLO size as 2 layers).  ``cfg.remat`` wraps
block bodies in ``jax.checkpoint`` for activation rematerialization.
"""

from __future__ import annotations

from functools import partial
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .config import ModelConfig
from .sharding import constrain
from .layers import (attention, attention_decode, attention_decode_slots,
                     cross_attention, init_attn, init_mlp, mlp, rmsnorm)
from .mla import init_mla, mla_attention, mla_decode
from .moe import init_moe, moe_layer
from ..core import sflog
from .ssm import init_ssm, ssm_scan, ssm_step
from .xlstm import (init_xlstm_pair, init_xlstm_state, xlstm_pair_scan,
                    xlstm_pair_step)

__all__ = ["init_params", "forward", "prefill", "decode_step",
           "decode_slots", "hymba_windows", "init_cache"]


# --------------------------------------------------------------------------
# init
# --------------------------------------------------------------------------
def hymba_windows(cfg: ModelConfig, s_max: int) -> np.ndarray:
    """Per-layer attention window: every ``global_layer_every``-th layer is
    global (window = s_max), the rest sliding-window."""
    w = np.full(cfg.n_layers, cfg.attn_window or s_max, dtype=np.int32)
    if cfg.global_layer_every:
        w[:: cfg.global_layer_every] = s_max
    return w


def init_params(key, cfg: ModelConfig) -> Dict:
    D, V, L = cfg.d_model, cfg.vocab, cfg.n_layers
    dt = jnp.dtype(cfg.dtype)
    keys = jax.random.split(key, 12)
    params: Dict = {
        "embed": (jax.random.normal(keys[0], (V, D)) * 0.02).astype(dt),
        "final_norm": jnp.ones((D,), dt),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = (jax.random.normal(keys[1], (D, V))
                             * 0.02).astype(dt)

    if cfg.block_kind == "xlstm":
        assert L % 2 == 0, "xlstm stacks (mLSTM, sLSTM) pairs"
        params["pairs"] = init_xlstm_pair(keys[2], cfg, L // 2)
        return params

    Ld = cfg.dense_layers
    blocks: Dict = {
        "ln1": jnp.ones((L - Ld, D), dt),
        "ln2": jnp.ones((L - Ld, D), dt),
        **_init_attn(keys[3], cfg, L - Ld),
    }
    if cfg.is_moe:
        blocks.update(init_moe(keys[4], cfg, L - Ld))
    elif cfg.d_ff:
        blocks.update(init_mlp(keys[5], cfg, L - Ld))
    if Ld:
        params["dense_blocks"] = {
            "ln1": jnp.ones((Ld, D), dt), "ln2": jnp.ones((Ld, D), dt),
            **_init_attn(keys[10], cfg, Ld), **init_mlp(keys[11], cfg, Ld)}
    if cfg.block_kind == "hymba":
        blocks.update(init_ssm(keys[6], cfg, L))
        blocks["ln_ssm_out"] = jnp.ones((L, D), dt)
        blocks["ln_attn_out"] = jnp.ones((L, D), dt)
    params["blocks"] = blocks

    if cfg.enc_layers:
        enc: Dict = {
            "ln1": jnp.ones((cfg.enc_layers, D), dt),
            "ln2": jnp.ones((cfg.enc_layers, D), dt),
            **init_attn(keys[7], cfg.scaled(n_layers=cfg.enc_layers),
                        cfg.enc_layers),
            **init_mlp(keys[8], cfg, cfg.enc_layers),
        }
        params["enc_blocks"] = enc
        params["enc_norm"] = jnp.ones((D,), dt)
    if cfg.cross_attention:
        params["cross_blocks"] = {
            "ln": jnp.ones((L, D), dt),
            **init_attn(keys[9], cfg, L),
        }
    return params


def _init_attn(key, cfg: ModelConfig, layers: int) -> Dict:
    return init_mla(key, cfg, layers) if cfg.is_mla else \
        init_attn(key, cfg, layers)


# --------------------------------------------------------------------------
# layer stacks
# --------------------------------------------------------------------------
def _stacks(params, cfg: ModelConfig):
    """(cache-key prefix, stacked block params, FFN kind, layer slice) of
    each scanned stack in order: the leading dense layers (``lead``), then
    the main stack (``moe``, ``mlp`` or ``None``)."""
    Ld, L = cfg.dense_layers, cfg.n_layers
    main = "moe" if cfg.is_moe else ("mlp" if cfg.d_ff else None)
    out = [("dense_", params["dense_blocks"], "lead", slice(0, Ld))] \
        if Ld else []
    return out + [("", params["blocks"], main, slice(Ld, L))]


def _cache_names(cfg: ModelConfig, prefix: str):
    """The two per-layer attention cache entries of a stack."""
    a, b = ("ckv", "kpe") if cfg.is_mla else ("k", "v")
    return prefix + a, prefix + b


def _attend(h, bp, cfg: ModelConfig, window, kernel: bool = False,
            lengths=None):
    """Full-sequence attention -> (output, the two cache entries);
    ``kernel``: forward-only kernels allowed (serving's prefill), which
    may skip the rows past each sequence's ``lengths``."""
    if cfg.is_mla:
        out, lat = mla_attention(h, bp, cfg, kernel=kernel, lengths=lengths)
        return out, (lat["ckv"], lat["kpe"])
    return attention(h, bp, cfg, window=window)


def _ffn(x, bp, cfg: ModelConfig, kind, valid=None):
    """Pre-norm FFN residual of one layer -> (x, aux)."""
    aux = jnp.zeros((), jnp.float32)
    if kind is None:
        return x, aux
    h2 = rmsnorm(x, bp["ln2"], cfg.norm_eps)
    if kind == "moe":
        ff, aux = moe_layer(h2, bp, cfg, valid=valid)
    elif kind == "lead":
        with sflog.scope("model.dense_mlp"):
            ff = mlp(h2, bp, cfg)
    else:
        ff = mlp(h2, bp, cfg)
    return x + ff, aux


# --------------------------------------------------------------------------
# block bodies
# --------------------------------------------------------------------------
def _block_train(x, bp, cfg: ModelConfig, window, kind, enc_kv=None,
                 cross_bp=None):
    """One decoder block, full sequence.  Returns (x, aux).

    With ``cfg.seq_shard`` the block boundary is *sequence-parallel*: the
    residual stream (and therefore the activation saved per layer by the
    remat scan) is sharded over the model axis along S, cutting saved-
    activation memory by the TP degree (Megatron-SP adapted to GSPMD)."""
    sd = 1 if cfg.seq_shard else None
    x = constrain(x, model_dim=sd)
    h = rmsnorm(x, bp["ln1"], cfg.norm_eps)
    attn_out, _ = _attend(h, bp, cfg, window)
    if cfg.block_kind == "hymba":
        ssm_out, _ = ssm_scan(h, bp, cfg)
        attn_out = rmsnorm(attn_out, bp["ln_attn_out"], cfg.norm_eps) + \
            rmsnorm(ssm_out, bp["ln_ssm_out"], cfg.norm_eps)
    x = x + attn_out
    if cross_bp is not None:
        xc = rmsnorm(x, cross_bp["ln"], cfg.norm_eps)
        x = x + cross_attention(xc, cross_bp, cfg, enc_kv)
    x, aux = _ffn(x, bp, cfg, kind)
    return constrain(x, model_dim=sd), aux


def _run_decoder_train(params, cfg: ModelConfig, x, windows,
                       enc_out=None):
    """Scan each decoder stack.  windows: (L,) per-layer window sizes."""
    cross = params.get("cross_blocks")
    aux = jnp.zeros((), jnp.float32)
    for _, blocks, kind, sl in _stacks(params, cfg):
        def body(carry, layer_in, kind=kind):
            x, aux = carry
            bp, win, cbp = layer_in
            enc_kv = None
            if cross is not None:
                B, Se, D = enc_out.shape
                Hkv, hd = cfg.n_kv_heads, cfg.hd
                ek = (enc_out @ cbp["wk"]).reshape(B, Se, Hkv, hd)
                ev = (enc_out @ cbp["wv"]).reshape(B, Se, Hkv, hd)
                enc_kv = (ek, ev)
            x, a = _block_train(x, bp, cfg, win, kind, enc_kv=enc_kv,
                                cross_bp=cbp)
            return (x, aux + a), None

        fn = body
        if cfg.remat == "block":
            fn = jax.checkpoint(body, prevent_cse=False)
        xs = (blocks, jnp.asarray(windows[sl]), cross)
        (x, aux), _ = jax.lax.scan(fn, (x, aux), xs)
    return x, aux


def _run_encoder(params, cfg: ModelConfig, x):
    def body(x, bp):
        h = rmsnorm(x, bp["ln1"], cfg.norm_eps)
        a, _ = attention(h, bp, cfg.scaled(n_layers=cfg.enc_layers),
                         causal=False)
        x = x + a
        h2 = rmsnorm(x, bp["ln2"], cfg.norm_eps)
        x = x + mlp(h2, bp, cfg)
        return x, None
    fn = body
    if cfg.remat == "block":
        fn = jax.checkpoint(body, prevent_cse=False)
    x, _ = jax.lax.scan(fn, x, params["enc_blocks"])
    return rmsnorm(x, params["enc_norm"], cfg.norm_eps)


def _head(params, cfg: ModelConfig, x):
    x = constrain(rmsnorm(x, params["final_norm"], cfg.norm_eps))
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    return constrain(x @ head, model_dim=x.ndim - 1)


# --------------------------------------------------------------------------
# training forward
# --------------------------------------------------------------------------
def forward(params, cfg: ModelConfig, *, tokens: Optional[jnp.ndarray] = None,
            embeds: Optional[jnp.ndarray] = None,
            enc_embeds: Optional[jnp.ndarray] = None
            ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """-> (logits (B, S, V), aux loss).  ``embeds`` overrides token lookup
    (VLM path); ``enc_embeds`` feeds the encoder (audio path)."""
    x = embeds if embeds is not None else jnp.take(params["embed"], tokens,
                                                   axis=0)
    x = constrain(x)
    B, S, D = x.shape
    if cfg.block_kind == "xlstm":
        def body(x, pp):
            st = init_xlstm_state(cfg, B)
            y, _ = xlstm_pair_scan(x, pp, cfg, st)
            return y, None
        fn = jax.checkpoint(body, prevent_cse=False) if cfg.remat == "block" \
            else body
        x, _ = jax.lax.scan(fn, x, params["pairs"])
        return _head(params, cfg, x), jnp.zeros((), jnp.float32)

    enc_out = None
    if cfg.enc_layers:
        enc_out = _run_encoder(params, cfg, enc_embeds)
    windows = hymba_windows(cfg, S) if cfg.block_kind == "hymba" else \
        np.full(cfg.n_layers, cfg.attn_window or S, dtype=np.int32)
    x, aux = _run_decoder_train(params, cfg, x, windows, enc_out=enc_out)
    return _head(params, cfg, x), aux


# --------------------------------------------------------------------------
# serving: prefill + decode
# --------------------------------------------------------------------------
def init_cache(cfg: ModelConfig, batch: int, s_max: int, dtype=None,
               enc_len: int = 1536) -> Dict:
    dt = dtype or jnp.dtype(cfg.dtype)
    L, Hkv, hd = cfg.n_layers, cfg.n_kv_heads, cfg.hd
    if cfg.block_kind == "xlstm":
        st = init_xlstm_state(cfg, batch)
        return {"pairs": jax.tree.map(
            lambda a: jnp.broadcast_to(a, (L // 2,) + a.shape), st),
            "pos": jnp.zeros((), jnp.int32)}
    cache = {"pos": jnp.zeros((), jnp.int32)}
    for prefix, n in (("dense_", cfg.dense_layers),
                      ("", L - cfg.dense_layers)):
        if not n:
            continue
        a, b = _cache_names(cfg, prefix)
        if cfg.is_mla:
            # the latent cache: c_kv and the shared rotary key per token
            cache[a] = jnp.zeros((n, batch, s_max, cfg.kv_lora_rank), dt)
            cache[b] = jnp.zeros((n, batch, s_max, cfg.qk_rope_dim), dt)
        else:
            cache[a] = jnp.zeros((n, batch, s_max, Hkv, hd), dt)
            cache[b] = jnp.zeros((n, batch, s_max, Hkv, hd), dt)
    if cfg.is_mla:
        sflog.counter("mla.latent_cache_bytes").value = sum(
            v.size * v.dtype.itemsize for k, v in cache.items() if k != "pos")
    if cfg.block_kind == "hymba":
        cache["h"] = jnp.zeros((L, batch, cfg.ssm_heads, cfg.hd,
                                cfg.ssm_state), jnp.float32)
    if cfg.cross_attention:
        # encoder K/V (normally overwritten by prefill; decode-only cells
        # lower against these shapes directly)
        cache["ck"] = jnp.zeros((L, batch, enc_len, Hkv, hd), dt)
        cache["cv"] = jnp.zeros((L, batch, enc_len, Hkv, hd), dt)
    return cache


def _last_x(x, last_pos):
    """Gather the per-row last *real* position from (B, S, D) activations —
    right-padded (length-bucketed) prompts read their logits at ``plen - 1``
    rather than at the pad tail."""
    if last_pos is None:
        return x[:, -1:]
    lp = jnp.asarray(last_pos, jnp.int32)
    return x[jnp.arange(x.shape[0]), lp][:, None]


def prefill(params, cfg: ModelConfig, *, tokens=None, embeds=None,
            enc_embeds=None, s_max: Optional[int] = None,
            last_pos: Optional[jnp.ndarray] = None
            ) -> Tuple[jnp.ndarray, Dict]:
    """Full-sequence forward that also returns decode caches.
    -> (logits of last position (B, V), cache).

    ``last_pos`` (B,) selects a per-row logit position for right-padded
    prompts (causal masking keeps real positions numerically unaffected by
    the pad tail; KV rows past ``last_pos`` hold pad junk that decode
    overwrites before its mask ever exposes them)."""
    x = embeds if embeds is not None else jnp.take(params["embed"], tokens,
                                                   axis=0)
    B, S, D = x.shape
    s_max = s_max or S
    if cfg.block_kind == "xlstm":
        def body(x, pp):
            st = init_xlstm_state(cfg, B)
            y, st = xlstm_pair_scan(x, pp, cfg, st)
            return y, st
        x, states = jax.lax.scan(body, x, params["pairs"])
        logits = _head(params, cfg, _last_x(x, last_pos))[:, 0]
        return logits, {"pairs": states, "pos": jnp.asarray(S, jnp.int32)}

    enc_out = None
    if cfg.enc_layers:
        enc_out = _run_encoder(params, cfg, enc_embeds)
    windows = hymba_windows(cfg, s_max) if cfg.block_kind == "hymba" else \
        np.full(cfg.n_layers, cfg.attn_window or s_max, dtype=np.int32)

    cross = params.get("cross_blocks")
    hymba = cfg.block_kind == "hymba"
    valid = lengths = None
    if last_pos is not None:
        # a bucketed prompt's pad tail attends to nothing (where a kernel
        # can skip it) and routes to no held expert
        lengths = jnp.asarray(last_pos) + 1
        if cfg.moe_held:
            valid = jnp.arange(S)[None] < lengths[:, None]

    def body(carry, layer_in, kind):
        x = carry
        bp, win, cbp = layer_in
        h = rmsnorm(x, bp["ln1"], cfg.norm_eps)
        attn_out, kv = _attend(h, bp, cfg, win, kernel=True, lengths=lengths)
        extras = {}
        if hymba:
            ssm_out, hstate = ssm_scan(h, bp, cfg)
            attn_out = rmsnorm(attn_out, bp["ln_attn_out"], cfg.norm_eps) + \
                rmsnorm(ssm_out, bp["ln_ssm_out"], cfg.norm_eps)
            extras["h"] = hstate
        x = x + attn_out
        if cbp is not None:
            xc = rmsnorm(x, cbp["ln"], cfg.norm_eps)
            Hkv, hd = cfg.n_kv_heads, cfg.hd
            Se = enc_out.shape[1]
            ek = (enc_out @ cbp["wk"]).reshape(B, Se, Hkv, hd)
            ev = (enc_out @ cbp["wv"]).reshape(B, Se, Hkv, hd)
            x = x + cross_attention(xc, cbp, cfg, (ek, ev))
            extras["ck"], extras["cv"] = ek, ev
        x, _ = _ffn(x, bp, cfg, kind, valid)
        # place into fixed-size cache (left-aligned)
        pad = s_max - S
        if pad:
            kv = tuple(jnp.pad(c, ((0, 0), (0, pad)) + ((0, 0),) * (c.ndim - 2))
                       for c in kv)
        return x, (kv, extras)

    cache = {"pos": jnp.asarray(S, jnp.int32)}
    for prefix, blocks, kind, sl in _stacks(params, cfg):
        xs = (blocks, jnp.asarray(windows[sl]), cross)
        x, (kv, extras) = jax.lax.scan(partial(body, kind=kind), x, xs)
        cache.update(zip(_cache_names(cfg, prefix), kv))
        cache.update(extras)
    logits = _head(params, cfg, _last_x(x, last_pos))[:, 0]
    return logits, cache


def decode_step(params, cfg: ModelConfig, tokens: jnp.ndarray, cache: Dict
                ) -> Tuple[jnp.ndarray, Dict]:
    """One decode step.  tokens: (B,) int32 -> (logits (B, V), cache')."""
    x = jnp.take(params["embed"], tokens[:, None], axis=0)
    B = x.shape[0]
    pos = cache["pos"]

    if cfg.block_kind == "xlstm":
        def body(x, layer_in):
            pp, st = layer_in
            y, st = xlstm_pair_step(x, pp, cfg, st)
            return y, st
        x, states = jax.lax.scan(body, x, (params["pairs"], cache["pairs"]))
        logits = _head(params, cfg, x)[:, 0]
        return logits, {"pairs": states, "pos": pos + 1}

    if cfg.is_mla or cfg.dense_layers:
        logits, cache = decode_slots(params, cfg, tokens, cache,
                                     jnp.full((B,), pos, jnp.int32))
        return logits, {**cache, "pos": pos + 1}

    s_max = cache["k"].shape[2]
    windows = hymba_windows(cfg, s_max) if cfg.block_kind == "hymba" else \
        np.full(cfg.n_layers, cfg.attn_window or s_max, dtype=np.int32)
    blocks = params["blocks"]
    cross = params.get("cross_blocks")
    hymba = cfg.block_kind == "hymba"

    def body(x, layer_in):
        bp, win, ck, cv, hst, cck, ccv, cbp = layer_in
        h = rmsnorm(x, bp["ln1"], cfg.norm_eps)
        attn_out, ck, cv = attention_decode(h, bp, cfg, ck, cv, pos,
                                            window=win)
        extras = {"k": ck, "v": cv}
        if hymba:
            ssm_out, hnew = ssm_step(h, bp, cfg, hst)
            attn_out = rmsnorm(attn_out, bp["ln_attn_out"], cfg.norm_eps) + \
                rmsnorm(ssm_out, bp["ln_ssm_out"], cfg.norm_eps)
            extras["h"] = hnew
        x = x + attn_out
        if cbp is not None:
            xc = rmsnorm(x, cbp["ln"], cfg.norm_eps)
            x = x + cross_attention(xc, cbp, cfg, (cck, ccv))
            extras["ck"], extras["cv"] = cck, ccv
        if cfg.is_moe:
            h2 = rmsnorm(x, bp["ln2"], cfg.norm_eps)
            ff, _ = moe_layer(h2, bp, cfg)
            x = x + ff
        elif cfg.d_ff:
            h2 = rmsnorm(x, bp["ln2"], cfg.norm_eps)
            x = x + mlp(h2, bp, cfg)
        return x, extras

    hs = cache.get("h") if hymba else jnp.zeros((cfg.n_layers,))
    cck = cache.get("ck") if cfg.cross_attention else \
        jnp.zeros((cfg.n_layers,))
    ccv = cache.get("cv") if cfg.cross_attention else \
        jnp.zeros((cfg.n_layers,))
    xs = (blocks, jnp.asarray(windows), cache["k"], cache["v"], hs, cck, ccv,
          cross)
    x, outs = jax.lax.scan(body, x, xs)
    new_cache = {"k": outs["k"], "v": outs["v"], "pos": pos + 1}
    if hymba:
        new_cache["h"] = outs["h"]
    if cfg.cross_attention:
        new_cache["ck"], new_cache["cv"] = outs["ck"], outs["cv"]
    logits = _head(params, cfg, x)[:, 0]
    return logits, new_cache


def decode_slots(params, cfg: ModelConfig, tokens: jnp.ndarray, cache: Dict,
                 positions: jnp.ndarray) -> Tuple[jnp.ndarray, Dict]:
    """One decode step in which each batch row has its own position (the
    serving engine's slots).  tokens, positions: (B,) -> (logits (B, V),
    cache with each row's new entry written at its position).  Attention
    is the slot form of the block's kind: GQA (``attention_decode_slots``)
    or MLA in its absorbed form over the latent cache (``mla_decode``)."""
    x = jnp.take(params["embed"], tokens[:, None], axis=0)
    attend = mla_decode if cfg.is_mla else attention_decode_slots
    cache = dict(cache)
    for prefix, blocks, kind, _ in _stacks(params, cfg):
        names = _cache_names(cfg, prefix)

        def body(x, layer_in, kind=kind):
            bp, c1, c2 = layer_in
            h = rmsnorm(x, bp["ln1"], cfg.norm_eps)
            out, c1, c2 = attend(h, bp, cfg, c1, c2, positions)
            x, _ = _ffn(x + out, bp, cfg, kind)
            return x, (c1, c2)

        x, new = jax.lax.scan(body, x, (blocks, cache[names[0]],
                                        cache[names[1]]))
        cache.update(zip(names, new))
    x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    return (x @ head)[:, 0], cache
