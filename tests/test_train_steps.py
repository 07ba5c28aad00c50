"""Integration: training steps end-to-end through the Session pipeline
(tune -> plan -> place -> train), fault tolerance (restart, node loss), the
data-plane invariants, and the partial-gradient (cluster hostsync) step's
equivalence to the single-program step.  (Formerly ``test_trainer.py`` —
the ``Trainer`` it was named for died in PR 3; the surviving cases live on
here under the name of what they actually test.)"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import FleetSpec, Session, SessionConfig, DriftDetected, WorkerLost
from repro.configs import smoke_config
from repro.core.hetero import BatchSchedule
from repro.core.privacy import Shard
from repro.models.api import get_model
from repro.optim import adamw
from repro.storage import DataConfig, SyntheticDevice, synth_sequence


def _spec(n_csds=2):
    return FleetSpec.demo(n_csds)


def _shards(n_csds=2):
    return _spec(n_csds).shards(
        private_per_worker={"csd": 64}, public=4096, prefix="priv"
    )


def _session(tmp_path=None, steps=6, n_csds=2):
    cfg = smoke_config("deepseek-7b")
    return Session(
        model=get_model(cfg),
        optimizer=adamw(),
        fleet=_spec(n_csds),
        data=DataConfig(vocab=cfg.vocab, seq_len=16),
        config=SessionConfig(
            total_steps=steps,
            checkpoint_dir=str(tmp_path) if tmp_path else None,
            checkpoint_every=2,
            async_checkpoint=False,
        ),
        shards=_shards(n_csds),
    )


def test_end_to_end_loss_decreases():
    s = _session(steps=8)
    assert s.plan().imbalance_steps() == 0
    report = s.run()
    assert report.final_loss < report.history[0]["loss"]


def test_restart_resumes_from_checkpoint(tmp_path):
    s = _session(tmp_path, steps=4)
    s.run()
    assert s.plan() is not None
    # second session resumes: runs only the remaining steps
    s2 = _session(tmp_path, steps=6)
    report = s2.run()
    assert report.steps_run == 2  # resumed at step 4 of 6


def test_worker_lost_replans():
    s = _session(steps=2, n_csds=3)
    n_groups = s.tune().schedule.n_groups
    s.apply(WorkerLost(["csd/1"]))
    assert s.tune().schedule.n_groups == n_groups - 1
    assert s.plan().imbalance_steps() == 0
    # the dead worker's private shard is gone — nobody else may read it
    assert all(sh.owner != "csd/1" for sh in s.shards if sh.private)
    report = s.run(steps=2)
    assert np.isfinite(report.final_loss)


def test_retune_keeps_shapes():
    s = _session(steps=2)
    shape_before = s.tune().schedule.global_rows
    s.apply(DriftDetected())
    assert s.tune().schedule.global_rows == shape_before  # no recompilation


# ---------------------------------------------------------------------------
# data plane (repro.storage)
# ---------------------------------------------------------------------------


def test_synth_deterministic_across_processes():
    cfg = DataConfig(vocab=1000, seq_len=32, seed=5)
    a = synth_sequence(cfg, "shard-x", 17)
    b = synth_sequence(cfg, "shard-x", 17)
    np.testing.assert_array_equal(a, b)
    c = synth_sequence(cfg, "shard-y", 17)
    assert not np.array_equal(a, c)


def test_private_store_enforces_ownership():
    cfg = DataConfig(vocab=100, seq_len=8)
    shards = [Shard("p", 10, True, "w0"), Shard("pub", 10, False)]
    s0 = SyntheticDevice("w0", cfg)
    s1 = SyntheticDevice("w1", cfg)
    s0.provision(shards)
    s1.provision(shards)
    s0.read("p", 0)             # owner: fine
    s1.read("pub", 0)           # public: fine
    with pytest.raises(PermissionError):
        s1.read("p", 0)         # private, non-owner: refused


def test_dataset_layout_and_masks():
    s = _session(steps=1)
    b = s.dataset.next_batch()
    R = s.tune().schedule.global_rows
    assert b["tokens"].shape == (R, 16)
    assert b["loss_mask"].shape == (R, 16)
    # mask matches the schedule exactly
    np.testing.assert_array_equal(
        b["loss_mask"][:, 0], s.tune().schedule.row_mask()
    )
    # invalid rows carry zero tokens (never sampled)
    dead = b["tokens"][b["loss_mask"][:, 0] == 0]
    assert (dead == 0).all()


# ---------------------------------------------------------------------------
# the cluster (hostsync) split step == the single-program step
# ---------------------------------------------------------------------------


def test_partial_grad_step_matches_train_step():
    """Summing per-host partial gradients and applying once must reproduce
    the fused masked-global-mean step exactly — the numerical contract the
    multi-process hostsync path stands on."""
    from repro.train.steps import (
        loss_fn, make_apply_step, make_partial_grad_step, make_train_step,
    )

    cfg = smoke_config("deepseek-7b")
    model = get_model(cfg)
    opt = adamw()
    params, _ = model.init_params(key=jax.random.PRNGKey(0))
    opt_state = opt.init(params)
    sched = lambda s: 1e-3  # noqa: E731

    rng = np.random.default_rng(0)
    R, S = 8, 8
    batch = {
        "tokens": rng.integers(0, cfg.vocab, (R, S)).astype(np.int32),
        "labels": rng.integers(0, cfg.vocab, (R, S)).astype(np.int32),
        # heterogeneous validity: one dead row per half
        "loss_mask": np.ones((R, S), np.float32),
    }
    batch["loss_mask"][3] = 0.0
    batch["loss_mask"][6] = 0.0

    fused = make_train_step(model, opt, sched)
    p_ref, o_ref, m_ref = fused(params, opt_state, batch)

    grad_step = make_partial_grad_step(model)
    apply_step = make_apply_step(opt, sched)
    halves = [
        {k: v[:4] for k, v in batch.items()},
        {k: v[4:] for k, v in batch.items()},
    ]
    grads, sums = None, None
    for h in halves:                       # the coordinator's tree-sum
        g, s = grad_step(params, h)
        if grads is None:
            grads, sums = g, s
        else:
            grads = jax.tree_util.tree_map(jnp.add, grads, g)
            sums = jax.tree_util.tree_map(jnp.add, sums, s)
    p_new, o_new, m_new = apply_step(params, opt_state, grads, sums)

    np.testing.assert_allclose(
        float(m_new["loss"]), float(m_ref["loss"]), rtol=1e-6
    )
    np.testing.assert_allclose(
        float(m_new["grad_norm"]), float(m_ref["grad_norm"]), rtol=1e-5
    )
    # the grad half: summed partial gradients / den == the fused gradient
    fused_grads = jax.grad(lambda p: loss_fn(model, p, batch)[0])(params)
    den = float(sums["den"])
    for a, b in zip(jax.tree_util.tree_leaves(grads),
                    jax.tree_util.tree_leaves(fused_grads)):
        np.testing.assert_allclose(
            np.asarray(a) / den, np.asarray(b), rtol=2e-5, atol=1e-7
        )
    # the apply half: fed the fused gradient, it lands on the fused params.
    # (Chaining the halves is not compared element-wise: Adam's first step
    # maps g to g / (|g| + eps), which near |g| ~ eps magnifies f32
    # summation-order noise in g past any parameter tolerance.)
    p_app, _, _ = apply_step(
        params, opt_state,
        jax.tree_util.tree_map(lambda g: g * den, fused_grads), sums,
    )
    for a, b in zip(jax.tree_util.tree_leaves(p_app),
                    jax.tree_util.tree_leaves(p_ref)):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-5, atol=1e-7
        )
    assert int(o_new.step) == int(o_ref.step) == 1


# ---------------------------------------------------------------------------
# the removed compat surfaces stay removed
# ---------------------------------------------------------------------------


def test_trainer_and_data_shims_are_gone():
    """Two PRs of deprecation are over: the ``Trainer`` stub and the
    ``repro.data`` pipeline shim no longer exist — stale imports fail at
    import time, not at behavior drift."""
    with pytest.raises(ImportError):
        import repro.train.trainer  # noqa: F401
    with pytest.raises(ImportError):
        import repro.data.pipeline  # noqa: F401
    import repro.train

    assert not hasattr(repro.train, "Trainer")


# ---------------------------------------------------------------------------
# int8-fused training precision
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("arch", ["deepseek-7b", "qwen3-moe-30b-a3b"])
def test_int8_fused_loss_trajectory_tracks_f32(arch):
    """train_precision='int8-fused' (quantized K/V + int8 residuals) tracks
    the f32 trajectory step for step on dense and MoE smoke models: measured
    divergence is <4% over the horizon; 8% is the documented tolerance."""
    from repro.train.steps import make_train_step

    cfg = smoke_config(arch)

    def run(prec, steps=6):
        m = get_model(cfg.with_(train_precision=prec))
        params, _ = m.init_params(key=jax.random.PRNGKey(0))
        opt = adamw()
        step = jax.jit(make_train_step(m, opt, lambda s: 1e-2))
        state = opt.init(params)
        losses = []
        key = jax.random.PRNGKey(3)
        B, S = 4, 16
        for t in range(steps):
            kt = jax.random.fold_in(key, t)
            # tokens from an eighth of the vocabulary: learnable unigram
            # structure (uniform tokens leave nothing to learn from ln(V))
            toks = jax.random.randint(kt, (B, S + 1), 0, cfg.vocab // 8)
            batch = {"tokens": toks[:, :-1], "labels": toks[:, 1:],
                     "loss_mask": jnp.ones((B, S), jnp.float32)}
            params, state, metrics = step(params, state, batch)
            losses.append(float(metrics["loss"]))
        return losses

    f32 = run("f32")
    q8 = run("int8-fused")
    np.testing.assert_allclose(q8, f32, rtol=0.08)
    assert f32[-1] < f32[0] and q8[-1] < q8[0]   # both actually learn


def test_int8_fused_shrinks_residual_bytes():
    """The int8 residual pytree is measurably smaller than f32's — the
    memory claim behind in-kernel low-precision training."""
    from repro.train.steps import abstract_batch, residual_bytes

    cfg = smoke_config("deepseek-7b").with_(remat=False, scan_layers=False)
    batch = abstract_batch(4, 16)
    f32 = residual_bytes(get_model(cfg), batch)
    q8 = residual_bytes(get_model(cfg.with_(train_precision="int8-fused")), batch)
    assert q8 < f32
