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

**Held experts** (``cfg.moe_held`` = n > 0, expert parallelism's share of
one chip): the layer holds experts ``[moe_held_offset, +n)`` of the
``moe_experts`` the router scores.  The router keeps its full width and
top-k; only picks to held experts make rows, sorted by expert, and every
other pick is left out (its part of the result lies on another chip).  The
SF plan has the tokens as roots and the held picks as leaves: dispatch is
a root->leaf ``bcast`` of each pick's token row, the experts run as one
grouped product (``lax.ragged_dot``) over rows sorted by expert, and
combine is the transpose leaf->root ``reduce`` (sum) of the weighted
expert rows onto their tokens.  Rows come in chunks of a static bound
(``moe_capacity`` times the expected held picks); a routing that sends
more picks here runs further chunks, so the layer is dropless for any
routing.  Scoring may be ``softmax`` or DeepSeek-V3's ``sigmoid`` with a
correction bias added for the choice only (``moe_score_bias``), the top-k
weights normalised and scaled by ``moe_route_scale``.
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

__all__ = ["init_moe", "moe_layer", "plan_cache", "grouped_rows"]

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
    Eh = cfg.moe_held or E                 # expert weights held here
    dt = jnp.dtype(cfg.dtype)
    ks = jax.random.split(key, 6)
    s = 1.0 / np.sqrt(D)
    so = 1.0 / np.sqrt(F) / np.sqrt(2 * cfg.n_layers)
    p = {
        "router": (jax.random.normal(ks[0], (layers, D, E)) * s).astype(jnp.float32),
        "w_in": (jax.random.normal(ks[1], (layers, Eh, D, F)) * s).astype(dt),
        "w_gate": (jax.random.normal(ks[2], (layers, Eh, D, F)) * s).astype(dt),
        "w_out": (jax.random.normal(ks[3], (layers, Eh, F, D)) * so).astype(dt),
    }
    if cfg.moe_score_bias:
        p["router_bias"] = jax.random.uniform(ks[5], (layers, E),
                                              jnp.float32, 0.0, 0.01)
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


def _route(logits, p: Dict, cfg: ModelConfig):
    """Router scores (T.., E), the top-k weights (f32) and expert ids."""
    k = cfg.moe_topk
    if cfg.moe_score == "sigmoid":
        scores = jax.nn.sigmoid(logits)
        choice = scores + p["router_bias"] if cfg.moe_score_bias else scores
        _, eidx = jax.lax.top_k(choice, k)
        wk = jnp.take_along_axis(scores, eidx, axis=-1)
        wk = wk / (jnp.sum(wk, axis=-1, keepdims=True) + 1e-20)
    else:
        scores = jax.nn.softmax(logits, axis=-1)
        wk, eidx = jax.lax.top_k(scores, k)
        wk = wk / jnp.sum(wk, axis=-1, keepdims=True)
    if cfg.moe_route_scale != 1.0:
        wk = wk * cfg.moe_route_scale
    return scores, wk, eidx


def _aux_loss(scores, eidx, cfg: ModelConfig):
    """Switch-style load-balance loss over the router's probabilities (the
    sigmoid scores normalised); top-1 counts via bincount, never the
    (.., E) one-hot buffer."""
    E = cfg.moe_experts
    if cfg.moe_score == "sigmoid":
        scores = scores / jnp.sum(scores, axis=-1, keepdims=True)
    me = jnp.mean(scores.reshape(-1, E), axis=0)
    cnt = jnp.zeros((E,), jnp.float32).at[eidx[..., 0].reshape(-1)].add(1.0)
    return E * jnp.sum(me * (cnt / eidx[..., 0].size))


def grouped_rows(T: int, cfg: ModelConfig) -> Tuple[int, int]:
    """(rows per chunk, chunks) of the held-expert layer for T tokens: the
    chunk is ``moe_capacity`` times the expected held picks under uniform
    routing (T k n / E), a multiple of 8; the chunks cover the most picks
    that can land here, T min(k, n)."""
    E, k, n = cfg.moe_experts, cfg.moe_topk, cfg.moe_held
    most = T * min(k, n)
    want = int(np.ceil(cfg.moe_capacity * T * k * n / E))
    rows = min(most, -(-want // 8) * 8)
    return rows, -(-most // rows)


def _moe_held(x, p: Dict, cfg: ModelConfig, valid):
    """The held-expert layer (module docstring): x (B, S, D) -> (y, aux)."""
    B, S, D = x.shape
    T, E, k, n = B * S, cfg.moe_experts, cfg.moe_topk, cfg.moe_held
    sflog.counter("moe.held_experts").value = n
    rows, chunks = grouped_rows(T, cfg)
    N = T * k
    with sflog.scope("moe.route"):
        xt = x.reshape(T, D)
        logits = xt.astype(jnp.float32) @ p["router"]
        scores, wk, eidx = _route(logits, p, cfg)
        local = eidx - cfg.moe_held_offset
        held = (local >= 0) & (local < n)
        if valid is not None:
            held = held & valid.reshape(T, 1)
        key = jnp.where(held, local, n).reshape(-1)
        # held picks first, grouped by expert; the rest never make a row
        order = jnp.argsort(key, stable=True).astype(jnp.int32)
        counts = jnp.zeros((n + 1,), jnp.int32).at[key].add(1)[:n]
        first = jnp.cumsum(counts) - counts
        nheld = jnp.sum(counts)
        pad = chunks * rows - N
        tok = jnp.pad(order // k, (0, max(pad, 0)))[:chunks * rows]
        wrow = jnp.pad(wk.reshape(-1)[order], (0, max(pad, 0)))[
            :chunks * rows]
        plan = _PLANS.get_or_build(
            ("held", T, rows, D, jnp.dtype(x.dtype).str),
            lambda: DynPlan(T, rows, label=("moe-held", T, rows, D)))

    def chunk(c, y):
        lo = c * rows
        live = lo + jnp.arange(rows) < nheld
        # leaf r -> root (token) of the r-th held pick; past the held picks
        # the drop row
        leaf_root = jnp.where(live, jax.lax.dynamic_slice(tok, (lo,),
                                                          (rows,)), T)
        sizes = jnp.clip(jnp.minimum(first + counts, lo + rows)
                         - jnp.maximum(first, lo), 0).astype(jnp.int32)
        with sflog.scope("moe.dispatch"):
            h = plan.bcast(xt, leaf_root)
        with sflog.scope("moe.experts"):
            up = jax.lax.ragged_dot(h, p["w_in"], sizes)
            gate = jax.lax.ragged_dot(h, p["w_gate"], sizes)
            out = jax.lax.ragged_dot(jax.nn.silu(gate) * up, p["w_out"],
                                     sizes)
        with sflog.scope("moe.combine"):
            w = jax.lax.dynamic_slice(wrow, (lo,), (rows,))
            return y + plan.reduce(out.astype(jnp.float32) * w[:, None],
                                   leaf_root, op="sum")

    y = chunk(0, jnp.zeros((T, D), jnp.float32))
    if chunks > 1:
        y = jax.lax.fori_loop(
            1, chunks, lambda c, y: jax.lax.cond(
                c * rows < nheld, chunk, lambda c, y: y, c, y), y)
    with sflog.scope("moe.route"):
        aux = _aux_loss(scores, eidx, cfg)
    return y.astype(x.dtype).reshape(B, S, D), aux


def moe_layer(x: jnp.ndarray, p: Dict, cfg: ModelConfig, *,
              groups: Optional[int] = None,
              dispatch: Optional[str] = None,
              valid: Optional[jnp.ndarray] = None
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
    scatter/gather formulation (same slots, same drops, same weights).

    With ``cfg.moe_held`` the held-expert layer runs instead (module
    docstring); ``valid`` (B, S) then marks the tokens that route (a
    bucketed prefill's pad tail makes no rows)."""
    if cfg.moe_held:
        y, aux = _moe_held(x, p, cfg, valid)
        return _shared(x, y, p, cfg), aux
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
        probs, wk, eidx = _route(logits, p, cfg)        # (G, T, k)
        wk = wk.astype(x.dtype)
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

    with sflog.scope("moe.route"):
        aux = _aux_loss(probs, eidx, cfg)
    return _shared(x, y, p, cfg), aux


def _shared(x, y, p: Dict, cfg: ModelConfig):
    """Add the shared expert (every token, on every chip) to ``y``."""
    if not cfg.moe_shared_ff:
        return y
    with sflog.scope("moe.experts"), sflog.scope("moe.shared"):
        return y + (jax.nn.silu(x @ p["shared_gate"])
                    * (x @ p["shared_in"])) @ p["shared_out"]
