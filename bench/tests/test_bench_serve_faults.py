"""A serving run with the timed path broken underneath comes out not
correct: the whole run past the look for a chip on a tiny copy of the
served model, once for each fault it can have: a decode step that returns
its cache unchanged, the MoE exchange (dispatch and combine) left out, and
a token altered where it is produced."""

import jax.numpy as jnp
import numpy as np
import pytest

from bench.tests import tiny

CELL = "phi35moe-chat32"


def test_sound_run_is_correct():
    res = tiny.run(CELL, seconds=1.0)
    assert res["correct"] is True
    assert res["metrics"]["serve_tokens_per_s"]["value"] > 0
    # the run's sweep winners ride on its line, before the checks
    line = list(res)
    assert isinstance(res["autotune_winners"], dict)
    assert line.index("autotune_winners") == len(line) - 2


@pytest.mark.parametrize("kind", ["unchanged", "no_exchange", "altered"])
def test_serve_fault_is_caught(monkeypatch, kind):
    from repro.models import moe, transformer
    from repro.serving.engine import ServeEngine

    if kind == "unchanged":
        decode = ServeEngine._decode_impl
        monkeypatch.setattr(ServeEngine, "_decode_impl", staticmethod(
            lambda cfg, params, tokens, cache, positions:
            (decode(cfg, params, tokens, cache, positions)[0], cache)))
    elif kind == "no_exchange":
        def no_moe(x, p, cfg, **kw):
            return jnp.zeros_like(x), jnp.zeros((), jnp.float32)
        monkeypatch.setattr(moe, "moe_layer", no_moe)
        monkeypatch.setattr(transformer, "moe_layer", no_moe)
    else:
        sample = ServeEngine._sample

        def altered(self, logits):
            out = np.array(sample(self, logits))
            out[0] = (out[0] + 1) % logits.shape[-1]
            return out
        monkeypatch.setattr(ServeEngine, "_sample", altered)
    assert tiny.run(CELL)["correct"] is False
