"""Training substrate: loss descent, microbatch equivalence, checkpoints,
elastic restore, fault tolerance, 8-bit optimizer."""

import os
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.training.checkpoint import (CheckpointManager, latest_step,
                                       load_checkpoint, save_checkpoint)
from repro.training.data import MemmapTokens, SyntheticLM, make_batch
from repro.training.fault import (SimulatedFailure, StragglerDetector,
                                  run_with_restarts)
from repro.training.optimizer import OptConfig, init_opt_state, lr_at
from repro.training.train_loop import (TrainConfig, TrainState,
                                       cross_entropy, make_train_step)


@pytest.fixture(scope="module")
def cfg():
    return get_config("qwen3-4b").smoke_config().scaled(dtype="float32",
                                                        remat="block")


def test_loss_decreases(cfg):
    key = jax.random.PRNGKey(0)
    ocfg = OptConfig(lr=1e-2, warmup_steps=5, decay_steps=100)
    st = TrainState.create(key, cfg, ocfg)
    step = jax.jit(make_train_step(cfg, ocfg, TrainConfig()))
    losses = []
    for i in range(30):
        b = {k: jnp.asarray(v) for k, v in
             make_batch(cfg, 8, 32, step=i % 4).items()}
        st.params, st.opt_state, m = step(st.params, st.opt_state, b)
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0] - 0.5


def test_microbatch_equivalence(cfg):
    key = jax.random.PRNGKey(0)
    ocfg = OptConfig()
    b = {k: jnp.asarray(v) for k, v in make_batch(cfg, 8, 32).items()}
    outs = []
    for G in (1, 4):
        st = TrainState.create(key, cfg, ocfg)
        step = jax.jit(make_train_step(cfg, ocfg, TrainConfig(microbatches=G)))
        p, o, m = step(st.params, st.opt_state, b)
        outs.append(p)
    d = max(float(jnp.max(jnp.abs(a - b_)))
            for a, b_ in zip(jax.tree.leaves(outs[0]),
                             jax.tree.leaves(outs[1])))
    assert d < 5e-3, d


@pytest.mark.parametrize("moments", ["float32", "bfloat16", "int8"])
def test_optimizer_moment_dtypes(cfg, moments):
    key = jax.random.PRNGKey(1)
    ocfg = OptConfig(moments_dtype=moments)
    st = TrainState.create(key, cfg, ocfg)
    step = jax.jit(make_train_step(cfg, ocfg, TrainConfig()))
    b = {k: jnp.asarray(v) for k, v in make_batch(cfg, 4, 16).items()}
    p, o, m = step(st.params, st.opt_state, b)
    assert np.isfinite(float(m["loss"]))
    if moments == "int8":
        leaf = jax.tree.leaves(o["m"])[0]
        assert leaf.dtype == jnp.int8 or any(
            l.dtype == jnp.int8 for l in jax.tree.leaves(o["m"]))


def test_int8_moments_track_fp32(cfg):
    """8-bit Adam must follow fp32 Adam closely over a few steps."""
    key = jax.random.PRNGKey(2)
    b = {k: jnp.asarray(v) for k, v in make_batch(cfg, 8, 32).items()}
    results = {}
    for moments in ("float32", "int8"):
        ocfg = OptConfig(lr=1e-3, moments_dtype=moments)
        st = TrainState.create(key, cfg, ocfg)
        step = jax.jit(make_train_step(cfg, ocfg, TrainConfig()))
        for _ in range(5):
            st.params, st.opt_state, m = step(st.params, st.opt_state, b)
        results[moments] = m["loss"]
    assert abs(float(results["int8"]) - float(results["float32"])) < 0.05


def test_lr_schedule():
    ocfg = OptConfig(lr=1e-3, warmup_steps=10, decay_steps=100,
                     min_lr_frac=0.1)
    assert float(lr_at(ocfg, jnp.asarray(0))) == 0.0
    assert abs(float(lr_at(ocfg, jnp.asarray(10))) - 1e-3) < 1e-9
    assert float(lr_at(ocfg, jnp.asarray(100))) == pytest.approx(1e-4,
                                                                 rel=1e-3)


def test_checkpoint_roundtrip_and_gc(cfg):
    key = jax.random.PRNGKey(0)
    st = TrainState.create(key, cfg, OptConfig())
    tree = {"params": st.params, "opt": st.opt_state}
    with tempfile.TemporaryDirectory() as d:
        mgr = CheckpointManager(d, keep=2, every=1)
        for s in (1, 2, 3, 4):
            mgr.maybe_save(s, tree, extra={"step": s})
        assert latest_step(d) == 4
        kept = sorted(os.listdir(d))
        assert len([k for k in kept if k.startswith("step_")]) == 2
        loaded, extra = load_checkpoint(d, 4, tree)
        for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(loaded)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        assert extra["step"] == 4


def test_checkpoint_bf16_leaves():
    tree = {"w": jnp.arange(8, dtype=jnp.bfloat16) * 0.5}
    with tempfile.TemporaryDirectory() as d:
        save_checkpoint(d, 1, tree)
        loaded, _ = load_checkpoint(d, 1, tree)
        np.testing.assert_array_equal(np.asarray(loaded["w"], np.float32),
                                      np.asarray(tree["w"], np.float32))


def test_elastic_restore_different_sharding(cfg):
    """Checkpoint saved from one layout restores under another (here:
    single-device -> single-device with explicit sharding objects), proving
    the mesh-agnostic path."""
    key = jax.random.PRNGKey(0)
    st = TrainState.create(key, cfg, OptConfig())
    with tempfile.TemporaryDirectory() as d:
        save_checkpoint(d, 5, {"params": st.params}, extra={"step": 5})
        from repro.launch.mesh import make_small_mesh
        mesh = make_small_mesh((1,), ("data",))
        from jax.sharding import NamedSharding, PartitionSpec as P
        sh = jax.tree.map(lambda _: NamedSharding(mesh, P()), st.params)
        loaded, _ = load_checkpoint(d, 5, {"params": st.params},
                                    shardings={"params": sh})
        for a, b in zip(jax.tree.leaves(st.params),
                        jax.tree.leaves(loaded["params"])):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_run_with_restarts_resumes():
    with tempfile.TemporaryDirectory() as d:
        mgr = CheckpointManager(d, every=2)
        seen = {"fail": False, "steps": []}

        def step_fn(step, state):
            seen["steps"].append(step)
            if step == 5 and not seen["fail"]:
                seen["fail"] = True
                raise SimulatedFailure("node died")
            state["tree"] = {"x": jnp.asarray(float(step))}
            return state

        state = {"tree": {"x": jnp.asarray(0.0)}, "step": 0}
        out = run_with_restarts(step_fn, state, mgr, total_steps=10,
                                max_restarts=2)
        assert out["step"] == 10
        assert seen["fail"]
        # resumed from checkpoint at step 4, not from zero
        assert seen["steps"].count(4) >= 2 or seen["steps"].count(5) >= 2


def test_straggler_detector():
    det = StragglerDetector(window=20, z_threshold=3.0)
    flags = [det.observe(0.1 + 0.001 * i) for i in range(20)]
    assert not any(flags)
    assert det.observe(1.5)


def test_straggler_detector_constant_history_no_false_positive():
    """Cold-start burst of IDENTICAL step times -> sd == 0; the sd floor
    must keep the next *normal* step (tiny jitter) from being flagged.
    Without the floor, (0.1001 - 0.1) / 1e-9 clears any threshold."""
    det = StragglerDetector(window=20, z_threshold=3.0)
    for _ in range(15):
        assert not det.observe(0.1)
    assert not det.observe(0.1001)       # 0.1% jitter: NOT a straggler
    assert not det.observe(0.105)        # 5% jitter: still within floor
    assert det.observe(0.5)              # a real 5x straggler still flags


def test_straggler_detector_relative_floor_scales_with_mean():
    """The floor is relative: the same ABSOLUTE jitter that is noise on
    slow steps is also noise on fast steps (floor = min_rel_sd * mean)."""
    det = StragglerDetector(window=20, z_threshold=3.0, min_rel_sd=0.05)
    for _ in range(12):
        det.observe(10.0)
    # 10.0 * 0.05 * 3.0 = 1.5 above the mean is the flag line
    assert not det.observe(11.0)
    assert det.observe(12.0)


def test_straggler_detector_window_eviction():
    """Only the trailing ``window`` observations form the baseline: after
    the window slides past a slow early era, the new fast era is the norm
    and an old-era time IS an outlier."""
    det = StragglerDetector(window=10, z_threshold=3.0)
    for _ in range(10):
        det.observe(1.0)                 # slow era
    for _ in range(10):
        det.observe(0.1)                 # fast era fills the whole window
    assert len(det.history) == 20        # history keeps everything...
    assert det.observe(1.0)              # ...but the window forgot the slow era
    det2 = StragglerDetector(window=100, z_threshold=3.0)
    for _ in range(10):
        det2.observe(1.0)
    for _ in range(10):
        det2.observe(0.1)
    assert not det2.observe(1.0)         # wide window still remembers it


def test_straggler_detector_warmup_never_flags():
    det = StragglerDetector(window=50)
    assert not any(det.observe(t) for t in
                   [0.1, 9.9, 0.1, 5.0, 0.1, 0.1, 0.1, 0.1, 0.1])


def test_data_determinism_and_resume():
    ds = SyntheticLM(vocab=100, seq_len=8, batch=4, seed=3)
    b1 = ds.batch_at(17)
    b2 = SyntheticLM(vocab=100, seq_len=8, batch=4, seed=3).batch_at(17)
    np.testing.assert_array_equal(b1["tokens"], b2["tokens"])
    # labels are next-token shifted
    np.testing.assert_array_equal(b1["labels"][:, :-1], b1["tokens"][:, 1:])
    # different ranks get different data
    b3 = SyntheticLM(vocab=100, seq_len=8, batch=4, seed=3, rank=1).batch_at(17)
    assert not np.array_equal(b1["tokens"], b3["tokens"])


def test_memmap_tokens(tmp_path):
    data = np.arange(10000, dtype=np.uint16) % 97
    f = tmp_path / "toks.bin"
    data.tofile(f)
    ds = MemmapTokens(str(f), vocab=97, seq_len=16, batch=4, world=2, rank=0)
    b1 = ds.batch_at(3)
    b2 = ds.batch_at(3)
    np.testing.assert_array_equal(b1["tokens"], b2["tokens"])
    assert b1["tokens"].shape == (4, 16)
    assert b1["tokens"].max() < 97


def test_cross_entropy_matches_naive(rng):
    logits = jnp.asarray(rng.standard_normal((2, 5, 11)).astype(np.float32))
    labels = jnp.asarray(rng.integers(0, 11, (2, 5)).astype(np.int32))
    got = float(cross_entropy(logits, labels))
    lf = np.asarray(logits, np.float64)
    lse = np.log(np.exp(lf).sum(-1))
    gold = np.take_along_axis(lf, np.asarray(labels)[..., None], -1)[..., 0]
    want = float((lse - gold).mean())
    assert abs(got - want) < 1e-4


def test_cross_entropy_masks_negative_labels(rng):
    logits = jnp.asarray(rng.standard_normal((1, 4, 7)).astype(np.float32))
    labels = jnp.asarray([[2, -1, 3, -1]], dtype=jnp.int32)
    got = float(cross_entropy(logits, labels))
    lf = np.asarray(logits, np.float64)
    lse = np.log(np.exp(lf).sum(-1))
    want = float(((lse[0, 0] - lf[0, 0, 2]) + (lse[0, 2] - lf[0, 2, 3])) / 2)
    assert abs(got - want) < 1e-4
