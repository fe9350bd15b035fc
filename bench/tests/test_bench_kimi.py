"""The ``kimik2-mooncake32`` cell's whole run past the look for a chip, on
a tiny copy of its configuration and traffic (``data/tiny-kimi.json``,
``data/tiny-mooncake.json``): a sound run is correct, also when the
held-expert layer needs many chunks, and comes out not correct with the
timed path broken underneath: held picks dropped at a row bound, a decode
step that returns its latent cache unchanged, a token altered where it is
produced.  Then the held experts a decode step reaches, as a run logs
them, and the latent-attention work counts."""

import time

import numpy as np
import pytest

from bench import harness, work_mla
from bench.tests import tiny

CELL = "kimik2-mooncake32"


def run(seed: int = 7, log=lambda *a: None):
    cfg = tiny.load("data/tiny-kimi.json")
    tr = tiny.load("data/tiny-mooncake.json")
    return harness.run_cell(CELL, seed, 1.0, False,
                            t_start=time.perf_counter(),
                            cell={"name": CELL, "chips": 1}, config=cfg,
                            traffic=tr, device=tiny.CPU, log=log)


def test_sound_run_is_correct():
    lines = []
    res = run(log=lines.append)
    assert res["correct"] is True
    assert res["metrics"]["serve_tokens_per_s"]["value"] > 0
    assert any(m.startswith("held experts hit a step: ") for m in lines)


def test_many_chunks_stay_exact(monkeypatch):
    """A row bound of 8 makes the held-expert layer run many chunks in
    prefill: the result is the same (dropless)."""
    from repro.models import moe

    def small(T, cfg):
        most = T * min(cfg.moe_topk, cfg.moe_held)
        return min(8, most), -(-most // min(8, most))
    monkeypatch.setattr(moe, "grouped_rows", small)
    assert run()["correct"] is True


@pytest.mark.parametrize("kind", ["dropped_picks", "unchanged", "altered"])
def test_kimi_fault_is_caught(monkeypatch, kind):
    from repro.models import moe
    from repro.serving.engine import ServeEngine

    if kind == "dropped_picks":
        # a capacity bound with no further chunk: picks past 4 rows vanish
        monkeypatch.setattr(moe, "grouped_rows", lambda T, cfg: (4, 1))
    elif kind == "unchanged":
        decode = ServeEngine._decode_impl
        monkeypatch.setattr(ServeEngine, "_decode_impl", staticmethod(
            lambda cfg, params, tokens, cache, positions:
            (decode(cfg, params, tokens, cache, positions)[0], cache)))
    else:
        sample = ServeEngine._sample

        def altered(self, logits):
            out = np.array(sample(self, logits))
            out[0] = (out[0] + 1) % logits.shape[-1]
            return out
        monkeypatch.setattr(ServeEngine, "_sample", altered)
    assert run()["correct"] is False


def test_held_hits_count_distinct_held_experts_a_step():
    """Four decoded tokens with two picks each in one MoE layer, experts
    4-7 held, dealt by the seed into two steps of two tokens: each step
    counts the distinct held experts its picks reach (a held expert picked
    twice counts once; picks to other chips' experts not at all)."""
    from types import SimpleNamespace
    from bench import seeds
    from bench.systems.mla_moe_lm import Serve
    lines = []
    me = SimpleNamespace(
        traffic={"slots": 2}, seed=1, log=lines.append,
        config={"held_expert_offset": 4, "n_held_experts": 4},
        mla=SimpleNamespace(topk=2, experts_hit=lambda m: 4 * m / 16))
    picks = np.array([[[4, 5], [5, 0], [7, 3], [9, 1]]])
    order = seeds.rng(1, 7).permutation(4)
    want = np.mean([len({e for t in step for e in picks[0, t] if 4 <= e < 8})
                    for step in (order[:2], order[2:])])
    Serve._log_held_hits(me, [picks[:, :3], picks[:, 3:]])
    assert lines == [f"held experts hit a step: {want:.6f} over 2 steps x "
                     f"layers (uniform routing: {1.0:.6f})"]


def test_mla_work_counts():
    cfg = tiny.load("../configs/kimi-k2-5l-ep48.json")
    s = work_mla.shape_of(cfg)
    assert s.mla_params == 101_122_048          # one layer's projections
    assert s.latent_row_bytes == 1152
    # a 32-sequence decode step's 256 picks reach ~3.9 of the 8 held
    assert abs(s.experts_hit(32 * 8) - 8 * (1 - (1 - 1 / 384) ** 256)) < 1e-12
    assert 3.8 < s.experts_hit(256) < 4.0
    ctx = [7000] * 32
    a = work_mla.attn_decode(s, ctx)
    assert a.flops == 5 * (2 * 64 * (512 + 64 + 512) * sum(ctx)
                           + 2 * 32 * 512 * 64 * 256)
    assert a.bytes == 5 * (1152 * sum(ctx) + 512 * 64 * 256 * 2)
    p = work_mla.attn_prefill(s, 4096)
    assert p.flops == 5 * (64 * 320 * 4096 * 4097 + 2 * 4096 * 512 * 64 * 256)
    # whole steps contain their attention
    assert work_mla.decode_step(s, ctx).flops > a.flops
    assert work_mla.prefill(s, 4096).flops > p.flops
    assert work_mla.decode_step(s, []).flops == 0.0
