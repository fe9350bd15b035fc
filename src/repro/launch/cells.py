"""Cell builder: (architecture × input shape × mesh) -> lowerable programs.

A *cell* is one entry of the assigned matrix: it binds an architecture
config, one of the four input shapes, per-cell run options (microbatching,
optimizer state dtype — the knobs that make the big configs fit), and the
mesh, and produces the jitted step function plus abstract inputs
(ShapeDtypeStruct — no allocation) with full in/out shardings, ready for
``.lower().compile()``.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..configs import SHAPES, get_config, shape_supported
from ..models import transformer as T
from ..models.config import ModelConfig
from ..models.sharding import (batch_spec, cache_specs, dp_axes, param_specs,
                               shardings)
from .mesh import make_small_mesh
from ..training.optimizer import OptConfig, init_opt_state
from ..training.train_loop import TrainConfig, make_train_step

__all__ = ["CellOptions", "cell_options", "build_cell", "abstractify"]

WHISPER_ENC_LEN = 1536   # stubbed mel-frame count (brief: frontend stub)


@dataclasses.dataclass(frozen=True)
class CellOptions:
    microbatches: int = 1
    moments_dtype: str = "float32"
    grad_dtype: str = "float32"
    remat: str = "block"
    seq_shard: bool = False


def cell_options(arch: str, shape: str) -> CellOptions:
    """Per-cell run options — the memory-fitting decisions (DESIGN.md §4.2)."""
    kind = SHAPES[shape]["kind"]
    if kind != "train":
        return CellOptions()
    big = arch in ("mistral-large-123b", "kimi-k2-1t-a32b", "llava-next-34b",
                   "qwen3-14b", "phi3.5-moe-42b-a6.6b")
    mb = 8 if big else 4
    if arch == "kimi-k2-1t-a32b":
        # 1T params: 8-bit moments + bf16 grad accumulation to fit 16 GB HBM
        return CellOptions(microbatches=16, moments_dtype="int8",
                           grad_dtype="bfloat16", seq_shard=True)
    if arch == "mistral-large-123b":
        return CellOptions(microbatches=mb, moments_dtype="bfloat16",
                           grad_dtype="bfloat16", seq_shard=True)
    if arch == "llava-next-34b":
        return CellOptions(microbatches=mb, moments_dtype="bfloat16",
                           seq_shard=True)
    return CellOptions(microbatches=mb)


def abstractify(tree):
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(jnp.shape(x), jnp.result_type(x)), tree)


def _opt_specs(params_specs, cfg_moments: str):
    """Optimizer-state specs mirroring the param specs (ZeRO-3)."""
    def leaf(ps):
        if cfg_moments == "int8":
            tail = list(ps) if ps is not None else []
            s_spec = P(*(tail[:-1] + [None])) if tail else P()
            return {"q": ps, "s": s_spec}
        return ps
    def walk(t):
        if isinstance(t, dict):
            return {k: walk(v) for k, v in t.items()}
        return leaf(t)
    return {"m": walk(params_specs), "v": walk(params_specs), "step": P()}


def _metric_specs(mesh: Mesh):
    rep = P()
    return {"loss": rep, "ce": rep, "aux": rep, "grad_norm": rep, "lr": rep}


def build_cell(arch: str, shape: str, mesh: Mesh,
               opts: Optional[CellOptions] = None,
               cfg_overrides: Optional[Dict[str, Any]] = None):
    """Returns dict(name, fn, args, in_shardings, out_shardings, donate,
    cfg, meta) or None if the (arch, shape) cell is skipped by design."""
    if not shape_supported(arch, shape):
        return None
    sh = SHAPES[shape]
    S, B, kind = sh["seq_len"], sh["global_batch"], sh["kind"]
    opts = opts or cell_options(arch, shape)
    cfg = get_config(arch).scaled(remat=opts.remat, seq_shard=opts.seq_shard,
                                  **(cfg_overrides or {}))

    key = jax.random.PRNGKey(0)
    params_abs = jax.eval_shape(lambda k: T.init_params(k, cfg), key)
    pspecs = param_specs(params_abs, cfg, mesh)
    dp = dp_axes(mesh)
    bs = P(dp)
    name = f"{arch}|{shape}|{'x'.join(str(s) for s in mesh.devices.shape)}"

    meta = {"arch": arch, "shape": shape, "kind": kind, "seq_len": S,
            "global_batch": B, "mesh": dict(mesh.shape),
            "params": cfg.param_count(),
            "active_params": cfg.active_param_count(),
            "options": dataclasses.asdict(opts)}

    if kind == "train":
        ocfg = OptConfig(moments_dtype=opts.moments_dtype)
        tcfg = TrainConfig(microbatches=opts.microbatches,
                           grad_dtype=opts.grad_dtype)
        opt_abs = jax.eval_shape(lambda p: init_opt_state(p, ocfg), params_abs)
        ospecs = _opt_specs(pspecs, opts.moments_dtype)
        batch_abs = {"tokens": jax.ShapeDtypeStruct((B, S), jnp.int32),
                     "labels": jax.ShapeDtypeStruct((B, S), jnp.int32)}
        bspecs = {"tokens": bs, "labels": bs}
        if cfg.family == "vlm":
            batch_abs = {"embeds": jax.ShapeDtypeStruct((B, S, cfg.d_model),
                                                        jnp.bfloat16),
                         "labels": batch_abs["labels"]}
            bspecs = {"embeds": P(dp, None, None), "labels": bs}
        if cfg.family == "audio":
            batch_abs["enc_embeds"] = jax.ShapeDtypeStruct(
                (B, WHISPER_ENC_LEN, cfg.d_model), jnp.bfloat16)
            bspecs["enc_embeds"] = P(dp, None, None)

        psh = shardings(mesh, pspecs)
        # microbatch-sliced batch shardings: (G, B/G, ...) with batch on dp
        mb_bsh = jax.tree.map(
            lambda s: NamedSharding(mesh, P(None, *s)), bspecs,
            is_leaf=lambda x: isinstance(x, P))
        fn = make_train_step(cfg, ocfg, tcfg, param_shardings=psh,
                             batch_shardings=mb_bsh
                             if opts.microbatches > 1 else None)
        in_sh = (psh, shardings(mesh, ospecs),
                 shardings(mesh, bspecs))
        out_sh = (shardings(mesh, pspecs), shardings(mesh, ospecs),
                  shardings(mesh, _metric_specs(mesh)))
        jfn = jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh,
                      donate_argnums=(0, 1))
        return dict(name=name, fn=jfn, args=(params_abs, opt_abs, batch_abs),
                    cfg=cfg, meta=meta)

    if kind == "prefill":
        inputs_abs: Dict[str, Any] = {}
        in_bspec: Dict[str, Any] = {}
        if cfg.family == "vlm":
            inputs_abs["embeds"] = jax.ShapeDtypeStruct((B, S, cfg.d_model),
                                                        jnp.bfloat16)
            in_bspec["embeds"] = P(dp, None, None)
        else:
            inputs_abs["tokens"] = jax.ShapeDtypeStruct((B, S), jnp.int32)
            in_bspec["tokens"] = bs
        if cfg.family == "audio":
            inputs_abs["enc_embeds"] = jax.ShapeDtypeStruct(
                (B, WHISPER_ENC_LEN, cfg.d_model), jnp.bfloat16)
            in_bspec["enc_embeds"] = P(dp, None, None)

        def prefill_fn(params, inputs):
            return T.prefill(params, cfg, s_max=S, **inputs)

        cache_abs = jax.eval_shape(lambda: T.init_cache(cfg, B, S))
        cspecs = cache_specs(cache_abs, cfg, mesh, B, S)
        msize = int(mesh.shape.get("model", 1))
        lspec = P(dp, "model") if cfg.vocab % msize == 0 else P(dp)
        # prefill's returned cache spec tree must match its actual structure
        cache_out_abs = jax.eval_shape(
            lambda p, i: prefill_fn(p, i)[1], params_abs, inputs_abs)
        cspecs_out = cache_specs(cache_out_abs, cfg, mesh, B, S)
        in_sh = (shardings(mesh, pspecs), shardings(mesh, in_bspec))
        out_sh = (NamedSharding(mesh, lspec), shardings(mesh, cspecs_out))
        jfn = jax.jit(prefill_fn, in_shardings=in_sh, out_shardings=out_sh)
        return dict(name=name, fn=jfn, args=(params_abs, inputs_abs),
                    cfg=cfg, meta=meta)

    # ---- decode: one new token against a seq_len KV cache
    def decode_fn(params, tokens, cache):
        return T.decode_step(params, cfg, tokens, cache)

    cache_abs = jax.eval_shape(lambda: T.init_cache(cfg, B, S))
    # position = S-1 (cache nearly full), tokens (B,)
    tok_abs = jax.ShapeDtypeStruct((B,), jnp.int32)
    cspecs = cache_specs(cache_abs, cfg, mesh, B, S)
    msize = int(mesh.shape.get("model", 1))
    lspec = P(dp if B % max(int(np.prod([mesh.shape[a] for a in dp])), 1) == 0
              and dp else None,
              "model" if cfg.vocab % msize == 0 else None)
    cache_out_abs = jax.eval_shape(decode_fn, params_abs, tok_abs, cache_abs)[1]
    cspecs_out = cache_specs(cache_out_abs, cfg, mesh, B, S)
    in_sh = (shardings(mesh, pspecs),
             NamedSharding(mesh, P(dp) if B % max(
                 int(np.prod([mesh.shape[a] for a in dp])), 1) == 0 and dp
                 else P(None)),
             shardings(mesh, cspecs))
    out_sh = (NamedSharding(mesh, lspec), shardings(mesh, cspecs_out))
    jfn = jax.jit(decode_fn, in_shardings=in_sh, out_shardings=out_sh,
                  donate_argnums=(2,))
    return dict(name=name, fn=jfn, args=(params_abs, tok_abs, cache_abs),
                cfg=cfg, meta=meta)


def input_specs(arch: str, shape: str = "train_4k",
                mesh: Optional[Mesh] = None):
    """ShapeDtypeStruct stand-ins for every model input of the cell —
    weak-type-correct, shardable, no device allocation (the brief's
    ``input_specs()`` contract).  Returns the abstract argument tuple that
    ``build_cell(...)['fn'].lower(*input_specs(...))`` accepts."""
    mesh = mesh or make_small_mesh((1, 1), ("data", "model"))
    cell = build_cell(arch, shape, mesh)
    if cell is None:
        raise ValueError(f"cell ({arch}, {shape}) is skipped by design")
    return cell["args"]
