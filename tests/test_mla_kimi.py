"""Latent attention, the leading dense layer and the held-expert MoE layer
(the Kimi-K2 block) against the plain reference ``bench/systems/
mla_moe_lm_ref.py``, on seeded random weights at a small size, in float32.

Tolerances: program and reference both compute in float32 here; they
differ by summation order (chunked online softmax against one softmax,
the absorbed decode's reassociated W_kvb products, grouped expert rows
against dense expert products), so they agree to float32 rounding grown
over three layers: 1e-4 relative to the largest logit, where computing
one side in bfloat16 misses by more than 1e-2."""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.systems import mla_moe_lm, mla_moe_lm_ref as ref
from repro.configs import get_config
from repro.models import moe, transformer as T
from repro.models.mla import mla_attention, mla_decode
from repro.serving.engine import ServeEngine, serve_cache_insert

HERE = os.path.dirname(os.path.abspath(__file__))
TINY = os.path.join(HERE, "..", "bench", "tests", "data", "tiny-kimi.json")
TOL = 1e-4


def _config(**kw):
    with open(TINY) as f:
        c = json.load(f)
    c.update(torch_dtype="float32", **kw)
    return c


def _weights(c, seed=0):
    p = ref.make_weights(c, jax.random.PRNGKey(seed))
    return jax.tree.map(lambda a: a.astype(jnp.float32), p)


def _close(got, want, tol=TOL):
    got, want = np.asarray(got), np.asarray(want)
    err = np.max(np.abs(got - want)) / np.max(np.abs(want))
    assert err < tol, err


@pytest.fixture(scope="module")
def model():
    c = _config()
    return c, mla_moe_lm.model_config(c), _weights(c)


def test_prefill_then_engine_decode_match_reference_forward(model):
    """A bucketed batch-1 prefill, inserted into slot 1 of a 3-slot cache,
    then the engine's per-slot decode through the latent cache: each
    step's logits equal the reference's full forward at that position."""
    c, cfg, params = model
    toks = np.asarray(jax.random.randint(jax.random.PRNGKey(5), (20,), 0,
                                         c["vocab_size"]))
    P, s_max = 11, 32
    want = np.asarray(ref.forward(params, toks, c))
    bucket = np.zeros((1, 16), np.int32)
    bucket[0, :P] = toks[:P]
    lg, cache1 = T.prefill(params, cfg, tokens=jnp.asarray(bucket),
                           s_max=s_max, last_pos=jnp.asarray([P - 1]))
    _close(lg[0], want[P - 1])
    cache = serve_cache_insert(T.init_cache(cfg, 3, s_max), cache1,
                               np.int32(1))
    positions = np.array([3, P, 7], np.int32)
    for t in range(P, 20):
        tokens = jnp.asarray([1, toks[t], 2], jnp.int32)
        lg, cache = ServeEngine._decode_impl(cfg, params, tokens, cache,
                                             jnp.asarray(positions))
        _close(lg[1], want[t])
        positions += 1


def test_absorbed_decode_equals_expanded(model):
    """MLA decode in the absorbed form over the latent cache gives the
    expanded full-sequence attention's output at the same position."""
    c, cfg, params = model
    lp = jax.tree.map(lambda a: a[0], params["blocks"])
    x = jax.random.normal(jax.random.PRNGKey(2), (1, 9, c["hidden_size"]))
    full, lat = mla_attention(x, lp, cfg)
    ckv = jnp.zeros((1, 16, cfg.kv_lora_rank)).at[:, :8].set(lat["ckv"][:, :8])
    kpe = jnp.zeros((1, 16, cfg.qk_rope_dim)).at[:, :8].set(lat["kpe"][:, :8])
    out, ckv2, kpe2 = mla_decode(x[:, 8:], lp, cfg, ckv, kpe,
                                 jnp.asarray([8]))
    _close(out[0, 0], full[0, 8])
    _close(ckv2[0, 8], lat["ckv"][0, 8])
    _close(kpe2[0, 8], lat["kpe"][0, 8])


def _layer(c, seed=1):
    """Layer 0 of the MoE stack with every expert of the tiny model held."""
    full = _config(n_held_experts=c["n_routed_experts"],
                   held_expert_offset=0)
    lp = jax.tree.map(lambda a: a[0], _weights(full, seed)["blocks"])
    return full, lp


def _share(lp, off, n):
    return dict(lp, **{k: lp[k][off:off + n]
                       for k in ("w_in", "w_gate", "w_out")})


def test_expert_shares_add_up_to_uncut_layer(model):
    """Four chips' shares (4 of 16 experts each), the shared expert
    counted once, add up to the uncut reference layer."""
    c, cfg, _ = model
    full, lp = _layer(c)
    h = jax.random.normal(jax.random.PRNGKey(4), (2, 12, c["hidden_size"]))
    whole = ref.moe_ffn(h.reshape(24, -1), lp, ref.dims(full))
    parts = []
    for off in range(0, 16, 4):
        shard = dataclasses.replace(cfg, moe_held=4, moe_held_offset=off)
        parts.append(moe.moe_layer(h, _share(lp, off, 4), shard)[0])
    # the shared expert alone: a share whose routed experts output zero
    no_routed = dict(lp, w_out=jnp.zeros_like(lp["w_out"][:4]),
                     w_in=lp["w_in"][:4], w_gate=lp["w_gate"][:4])
    shared = moe.moe_layer(h, no_routed, dataclasses.replace(
        cfg, moe_held=4, moe_held_offset=0))[0]
    total = sum(parts) - 3 * shared
    _close(total.reshape(24, -1), whole)


def test_dropless_when_every_token_picks_the_same_held_experts(model):
    """A correction bias that sends every token's top-k to the held
    experts: all T*k picks land here, past the row bound, and still every
    one is computed (the layer runs further chunks)."""
    c, cfg, _ = model
    full, lp = _layer(c, seed=3)
    off, n = cfg.moe_held_offset, cfg.moe_held
    bias = jnp.zeros_like(lp["router_bias"]).at[off:off + n].set(10.0)
    lp = dict(lp, router_bias=bias)
    h = jax.random.normal(jax.random.PRNGKey(6), (2, 16, c["hidden_size"]))
    T, k = 32, cfg.moe_topk
    rows, chunks = moe.grouped_rows(T, cfg)
    assert rows < T * k and chunks > 1
    got = moe.moe_layer(h, _share(lp, off, n), cfg)[0]
    d = ref.dims(c)._replace(offset=off)
    want = ref.moe_ffn(h.reshape(T, -1), dict(lp, **_share(lp, off, n)), d)
    _close(got.reshape(T, -1), want)


def test_sigmoid_bias_scaled_routing_matches_reference(model):
    c, cfg, _ = model
    _, lp = _layer(c, seed=4)
    lp = dict(lp, router_bias=lp["router_bias"] * 20)   # bias moves picks
    h = jax.random.normal(jax.random.PRNGKey(8), (24, c["hidden_size"]))
    _, wk, eidx = moe._route(h @ lp["router"], lp, cfg)
    idx, top = ref.route(h, lp, ref.dims(c))
    np.testing.assert_array_equal(np.asarray(eidx), np.asarray(idx))
    np.testing.assert_allclose(np.asarray(wk), np.asarray(top), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(wk).sum(-1), 2.827, rtol=1e-6)


def test_leading_dense_layer_runs_before_moe_stack(model, monkeypatch):
    c, cfg, params = model
    order = []
    mlp, layer = T.mlp, T.moe_layer
    monkeypatch.setattr(T, "mlp", lambda *a, **k: (order.append("dense"),
                                                    mlp(*a, **k))[1])
    monkeypatch.setattr(T, "moe_layer", lambda *a, **k: (
        order.append("moe"), layer(*a, **k))[1])
    toks = jnp.zeros((1, 8), jnp.int32)
    logits, cache = T.prefill(params, cfg, tokens=toks, s_max=8)
    runs = [o for i, o in enumerate(order) if i == 0 or order[i - 1] != o]
    assert runs == ["dense", "moe"]
    assert params["dense_blocks"]["w_in"].shape == (1, 64, 96)
    assert set(cache) == {"pos", "dense_ckv", "dense_kpe", "ckv", "kpe"}
    _close(logits[0], ref.forward(params, np.zeros(8, np.int32), c)[-1])


def test_published_kimi_sizes_and_counts():
    """The configuration is the published one, and its parameter counts
    come within 5% of the published 1T total and 32B activated."""
    cfg = get_config("kimi-k2-1t-a32b")
    assert (cfg.is_mla, cfg.kv_lora_rank, cfg.q_lora_rank, cfg.n_heads,
            cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim) == \
        (True, 512, 1536, 64, 128, 64, 128)
    assert (cfg.dense_layers, cfg.d_ff, cfg.moe_experts, cfg.moe_topk,
            cfg.moe_score, cfg.moe_route_scale) == \
        (1, 18432, 384, 8, "sigmoid", 2.827)
    assert abs(cfg.param_count() / 1.0e12 - 1) < 0.05, cfg.param_count()
    assert abs(cfg.active_param_count() / 32e9 - 1) < 0.05, \
        cfg.active_param_count()


def test_smoke_param_count_tracks_init():
    cfg = get_config("kimi-k2-1t-a32b").smoke_config().scaled(
        dtype="float32")
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    actual = sum(x.size for x in jax.tree.leaves(params))
    assert abs(cfg.param_count() - actual) / actual < 0.05
