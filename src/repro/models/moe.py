"""Mixture-of-Experts layer with star-forest capacity dispatch.

The token→expert-slot assignment is literally a star forest (tokens = leaves,
expert slots = roots; DESIGN.md §4, paper §2): every step the router's top-k
picks define the leaf→root edge list of a :class:`repro.core.DynPlan` —
dispatch is a leaf→root ``reduce`` with capacity-drop semantics (overflowing
picks land on the plan's drop row and vanish), combine is a root→leaf
``bcast`` of the weighted expert outputs.  The plan *skeleton* is cached per
``(G, T, k, E, C, D, dtype)`` signature (:func:`plan_cache`), so repeated
decode steps reuse the tuned gather closures instead of re-deriving index
machinery, and a :class:`repro.core.FieldBundle` fuses the hidden-state
``(D,)`` payload with the combine-weight payload into ONE scatter.

The legacy dense formulation (per-group scatter-add/gather-einsum) is kept
as ``dispatch="dense"``; both paths share the same sort-based slot ranking
(:func:`_capacity_slots`), so drops and weights are *identical* — the SF
path is a communication-layer rewiring, not a new algorithm.  Select with
``cfg.moe_dispatch`` or the ``dispatch=`` override.

Grouping: tokens are dispatched in G independent groups, so the sort never
crosses the data-parallel shard boundary — G = batch rows for training
shapes, G = 1 for tiny decode batches (auto).

Expert weights are stacked (E, D, F) and sharded over the model axis (EP)
and the data axis (FSDP); the expert compute is a single einsum over the
sharded buffer, which is what the MXU wants.
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .config import ModelConfig
from ..core import sflog
from ..core.dynplan import DynPlan, PlanCache
from ..core.fields import FieldBundle

__all__ = ["init_moe", "moe_layer", "plan_cache"]

# module-level skeleton cache: one DynPlan per dispatch signature, shared by
# every layer/step with the same (G, T, k, E, C, D, dtype) problem.  The
# serving benchmark reads its hit rate.
_PLANS = PlanCache("moe-dispatch")

# measured crossover for the dispatch lowering: at decode-sized leaf counts
# the fused two-field FieldBundle exchange wins (fewer kernel launches); at
# prefill-sized counts the leaf_rep-composed gather wins (~25% — it skips
# the materialized k-way repeat of the hidden state)
_FUSE_MAX_LEAVES = 64


def plan_cache() -> PlanCache:
    """The process-wide MoE dispatch plan cache (hits/misses feed
    ``BENCH_serving.json``)."""
    return _PLANS


def init_moe(key, cfg: ModelConfig, layers: int) -> Dict:
    D, E, F = cfg.d_model, cfg.moe_experts, cfg.moe_dff
    dt = jnp.dtype(cfg.dtype)
    ks = jax.random.split(key, 5)
    s = 1.0 / np.sqrt(D)
    so = 1.0 / np.sqrt(F) / np.sqrt(2 * cfg.n_layers)
    p = {
        "router": (jax.random.normal(ks[0], (layers, D, E)) * s).astype(jnp.float32),
        "w_in": (jax.random.normal(ks[1], (layers, E, D, F)) * s).astype(dt),
        "w_gate": (jax.random.normal(ks[2], (layers, E, D, F)) * s).astype(dt),
        "w_out": (jax.random.normal(ks[3], (layers, E, F, D)) * so).astype(dt),
    }
    if cfg.moe_shared_ff:
        Fs = cfg.moe_shared_ff
        k1, k2, k3 = jax.random.split(ks[4], 3)
        p["shared_in"] = (jax.random.normal(k1, (layers, D, Fs)) * s).astype(dt)
        p["shared_gate"] = (jax.random.normal(k2, (layers, D, Fs)) * s).astype(dt)
        p["shared_out"] = (jax.random.normal(k3, (layers, Fs, D)) * so).astype(dt)
    return p


def _capacity_slots(eidx, C: int, E: int):
    """Slot ranking for one group — the shared half of both dispatch paths.

    eidx: (T, k) expert ids.  Returns (slot (T, k) in [0, E*C] with E*C the
    drop slot, keep (T, k)).  A per-group stable sort by expert id replaces
    the fetch-and-add slot allocation: rank within the expert run beyond the
    capacity C is dropped.  Each non-drop slot has exactly ONE writer, which
    is what makes dense and SF dispatch bit-identical.
    """
    T, k = eidx.shape
    flat_e = eidx.reshape(-1)
    order = jnp.argsort(flat_e, stable=True)
    sorted_e = flat_e[order]
    # rank within expert run
    first = jnp.searchsorted(sorted_e, jnp.arange(E), side="left")
    pos = jnp.arange(T * k) - first[sorted_e]
    keep_s = pos < C
    slot_s = jnp.where(keep_s, sorted_e * C + pos, E * C)  # E*C = drop slot
    # un-sort slot/keep to (T, k) order
    inv = jnp.argsort(order, stable=True)
    return slot_s[inv].reshape(T, k), keep_s[inv].reshape(T, k)


def _dispatch_dense(xg, slot, keep, C: int, E: int):
    """Legacy dense dispatch: per-group scatter-add into the (E*C+1, D)
    buffer (trailing drop row trimmed)."""

    def one(x1, slot1, keep1):
        T, k = slot1.shape
        tok = jnp.repeat(jnp.arange(T), k)
        buf = jnp.zeros((E * C + 1, x1.shape[1]), x1.dtype)
        buf = buf.at[slot1.reshape(-1)].add(
            x1[tok] * keep1.reshape(-1)[:, None].astype(x1.dtype))
        return buf[:-1]

    return jax.vmap(one)(xg, slot, keep)


def routing_leaf_root(slot, keep, C: int, E: int) -> jnp.ndarray:
    """Flatten per-group slots to the DynPlan edge list: leaf i (= pick
    ``(g, t, j)`` in row-major order) points at root ``g*E*C + slot`` —
    dropped picks point one past the last root (``G*E*C``)."""
    G = slot.shape[0]
    if G == 1:
        # single group (decode shape): the local drop sentinel E*C already
        # IS the global one — the per-group rebase is a no-op
        return slot.reshape(-1)
    base = (jnp.arange(G) * (E * C))[:, None, None]
    gslot = jnp.where(keep, slot + base, G * E * C)
    return gslot.reshape(-1)


def _moe_plan(G: int, T: int, k: int, E: int, C: int, D: int,
              dtype) -> DynPlan:
    sig = (G, T, k, E, C, D, jnp.dtype(dtype).str)
    return _PLANS.get_or_build(
        sig, lambda: DynPlan(G * E * C, G * T * k, label=("moe",) + sig))


def moe_layer(x: jnp.ndarray, p: Dict, cfg: ModelConfig, *,
              groups: Optional[int] = None,
              dispatch: Optional[str] = None
              ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x: (B, S, D) -> (y, aux_loss).  Router in fp32; top-k softmax over the
    selected logits; capacity C = ceil(S_g * k * cf / E) per group.

    The expert einsums run on the full (G, E, C, D) buffer *outside* the
    per-group slot ranking so the EP sharding constraints (groups over dp,
    experts over model) pin the buffer layout — the scatter into / gather
    out of it IS the SF exchange (``dispatch="sf"``, the default via
    ``cfg.moe_dispatch``): dispatch = fused leaf→root reduce of the hidden
    state + combine weight, combine = root→leaf bcast of the weighted
    expert outputs.  ``dispatch="dense"`` keeps the legacy per-group
    scatter/gather formulation (same slots, same drops, same weights)."""
    from .sharding import constrain
    mode = dispatch if dispatch is not None \
        else getattr(cfg, "moe_dispatch", "sf")
    if mode not in ("sf", "dense"):
        raise ValueError(f"unknown moe dispatch mode {mode!r}")
    B, S, D = x.shape
    E, k = cfg.moe_experts, cfg.moe_topk
    G = groups if groups is not None else (B if S > 1 else 1)
    T = (B * S) // G
    C = max(int(np.ceil(T * k * cfg.moe_capacity / E)), 1)

    with sflog.scope("moe.route"):
        xg = constrain(x.reshape(G, T, D))
        logits = constrain(jnp.einsum("gtd,de->gte", xg.astype(jnp.float32),
                                      p["router"]))
        probs = jax.nn.softmax(logits, axis=-1)
        wk, eidx = jax.lax.top_k(probs, k)              # (G, T, k)
        wk = (wk / jnp.sum(wk, axis=-1, keepdims=True)).astype(x.dtype)
        slot, keep = jax.vmap(lambda e1: _capacity_slots(e1, C, E))(eidx)
        if mode == "sf":
            plan = _moe_plan(G, T, k, E, C, D, x.dtype)
            leaf_root = routing_leaf_root(slot, keep, C, E)

    with sflog.scope("moe.dispatch"):
        if mode == "sf":
            w_leaf = wk.reshape(G * T * k, 1)
            # capacity slots never repeat -> one writer per root, so the
            # reduce lowers as invert-permutation + tuned gather
            # (unique=True)
            if G * T * k <= _FUSE_MAX_LEAVES:
                # decode-sized: leaves carry the pick's hidden state + its
                # combine weight; same dtype -> FieldBundle fuses both into
                # ONE drop-guarded exchange
                x_leaf = jnp.repeat(xg.reshape(G * T, D), k, axis=0)
                bound = plan.bind(leaf_root, unique=True)
                fb = FieldBundle.for_data(bound, [x_leaf, w_leaf])
                buf, sw = fb.reduce_multi(
                    [x_leaf, w_leaf],
                    [jnp.zeros((G * E * C, D), x.dtype),
                     jnp.zeros((G * E * C, 1), x.dtype)], op="sum")
            else:
                # prefill-sized: the materialized repeat+concat dominates,
                # so compose the exchange with the token->pick replication
                # map instead (leaf_rep, the PetscSFCompose shortcut) and
                # gather the hidden state straight from the compact token
                # rows; the weight payload shares the same inverted-writer
                # plan (CSE'd under jit into one inversion)
                buf = plan.reduce(xg.reshape(G * T, D), leaf_root, op="sum",
                                  unique=True, leaf_rep=k)
                sw = plan.reduce(w_leaf, leaf_root, op="sum", unique=True)
        else:
            buf = _dispatch_dense(xg, slot, keep, C, E)
        h = constrain(buf.reshape(G, E, C, D), model_dim=1)   # EP layout

    with sflog.scope("moe.experts"):
        up = jnp.einsum("gecd,edf->gecf", h, p["w_in"])
        gate = jnp.einsum("gecd,edf->gecf", h, p["w_gate"])
        out = jnp.einsum("gecf,efd->gecd", jax.nn.silu(gate) * up,
                         p["w_out"])
        out_flat = constrain(out.reshape(G, E * C, D))

    with sflog.scope("moe.combine"):
        if mode == "sf":
            # weight at the root (each slot has exactly one writer, so w*out
            # here is bit-identical to weighting at the leaf), then bcast
            # back: dropped picks read the zero drop row.  Sum over k as
            # unrolled slice adds — XLA lowers this ~3x faster than reduce
            # over the k axis at these shapes.
            scaled = out_flat.reshape(G * E * C, D) * sw
            picks = plan.bcast(scaled, leaf_root).reshape(G, T, k, D)
            y = picks[:, :, 0]
            for j in range(1, k):
                y = y + picks[:, :, j]
            y = y.reshape(B, S, D)
        else:
            def combine(of, slot1, keep1, w1):
                gathered = of[jnp.minimum(slot1, E * C - 1)]  # (T, k, D)
                gathered = gathered * keep1[..., None].astype(of.dtype)
                return jnp.einsum("tkd,tk->td", gathered,
                                  w1.astype(of.dtype))

            y = jax.vmap(combine)(out_flat, slot, keep, wk).reshape(B, S, D)

    # load-balance aux loss (Switch-style); top-1 counts via bincount —
    # never materializes the (G, T, E) one-hot buffer
    with sflog.scope("moe.route"):
        me = jnp.mean(probs, axis=(0, 1))                   # (E,)
        cnt = jnp.zeros((E,), jnp.float32).at[
            eidx[..., 0].reshape(-1)].add(1.0)
        ce = cnt / (G * T)
        aux = E * jnp.sum(me * ce)

    if cfg.moe_shared_ff:
        with sflog.scope("moe.experts"):
            shared = (jax.nn.silu(x @ p["shared_gate"])
                      * (x @ p["shared_in"])) @ p["shared_out"]
            y = y + shared
    return y, aux
