"""Session API contract: staged frozen/cached artifacts, the unified
elastic-event path (WorkerLost == old drop_workers semantics; DriftDetected
keeps compiled shapes — compile-count probe), the callback registry, and the
fleet-aware placement manifest."""
import dataclasses

import numpy as np
import pytest

from repro.api import (
    CallbackRegistry, DriftDetected, FleetSpec, Session, SessionConfig,
    TunePlan, WorkerJoined, WorkerLost,
)
from repro.configs import smoke_config
from repro.models.api import get_model
from repro.optim import adamw
from repro.storage import DataConfig


def _session(n_csds=2, steps=4, callbacks=None, seq_len=16):
    cfg = smoke_config("deepseek-7b")
    spec = FleetSpec.demo(n_csds)
    return Session(
        model=get_model(cfg),
        optimizer=adamw(),
        fleet=spec,
        data=DataConfig(vocab=cfg.vocab, seq_len=seq_len),
        shards=spec.shards(private_per_worker={"csd": 64}, public=4096),
        config=SessionConfig(total_steps=steps),
        callbacks=callbacks,
    )


# ---------------------------------------------------------------------------
# stage artifacts: cached, frozen, overridable
# ---------------------------------------------------------------------------


def test_stages_cached_and_frozen():
    s = _session()
    tp = s.tune()
    assert s.tune() is tp                      # memoized: same object
    assert s.plan() is s.plan()
    assert s.place() is s.place()
    with pytest.raises(dataclasses.FrozenInstanceError):
        tp.schedule = None                     # artifacts are immutable
    with pytest.raises(dataclasses.FrozenInstanceError):
        s.plan().steps_per_epoch = 0


def test_stages_lazy_until_accessed():
    s = _session()
    assert not s.cached("tune")
    s.plan()                                   # pulls tune() implicitly
    assert s.cached("tune") and s.cached("plan") and not s.cached("place")


def test_override_invalidates_downstream():
    s = _session()
    s.place()
    old_tp = s.tune()
    forced = TunePlan(
        result=old_tp.result,
        schedule=old_tp.schedule.with_batches(
            [max(1, b - 1) for b in old_tp.schedule.group_batches]
        ),
        group_workers=old_tp.group_workers,
    )
    s.override("tune", forced)
    assert s.tune() is forced
    assert not s.cached("plan") and not s.cached("place")
    # downstream stages rebuild against the override
    assert s.plan().imbalance_steps() == 0


def test_override_unknown_stage_rejected():
    with pytest.raises(KeyError):
        _session().override("nope", object())


# ---------------------------------------------------------------------------
# the unified elastic-event path
# ---------------------------------------------------------------------------


def test_worker_lost_matches_drop_workers_semantics():
    s = _session(n_csds=3)
    tp = s.tune()
    n_groups, max_local = tp.schedule.n_groups, tp.schedule.max_local
    res = s.apply(WorkerLost(["csd/1"]))
    tp2 = s.tune()
    assert tp2.schedule.n_groups == n_groups - 1
    assert "csd/1" not in tp2.group_workers
    assert s.plan().imbalance_steps() == 0     # Eq. 1 re-balanced
    # dead worker's private shard is gone — nobody else may read it
    assert res.dropped_shards == ("private-csd/1",)
    assert all(sh.owner != "csd/1" for sh in s.shards if sh.private)
    # the capacity fix: max_local survives the node loss (no avoidable
    # shape change beyond the group-count shrink)
    assert tp2.schedule.max_local == max_local


def test_worker_lost_unknown_worker_raises():
    s = _session()
    with pytest.raises(KeyError):
        s.apply(WorkerLost(["csd/99"]))


def test_worker_joined_grows_fleet_through_same_path():
    s = _session(n_csds=2)
    before = s.tune().schedule
    s.apply(WorkerJoined("csd", 2))
    after = s.tune()
    assert after.schedule.n_groups == before.n_groups + 2
    assert s.fleet.by_name("csd").count == 4
    assert s.plan().imbalance_steps() == 0
    # capacity never shrinks across events
    assert after.schedule.max_local >= before.max_local


def test_drift_retune_keeps_compiled_shapes():
    s = _session(steps=2)
    s.run()                                    # builds + uses the step
    compiled = s.compile()
    count = s.compile_count
    res = s.apply(DriftDetected())
    assert not res.recompiled                  # shapes pinned by capacity
    assert s.compile() is compiled             # same jitted step object
    assert s.compile_count == count            # the probe: zero rebuilds
    assert s.tune().schedule.global_rows == compiled.global_rows
    # and the pipeline still trains through the surviving step
    report = s.run(steps=1)
    assert np.isfinite(report.final_loss)


def test_drift_after_worker_lost_uses_shrunk_fleet():
    s = _session(n_csds=3)
    s.apply(WorkerLost(["csd/1"]))
    assert s.fleet.by_name("csd").count == 2   # fleet membership is live
    s.apply(DriftDetected())                   # must not resurrect csd/1
    assert s.tune().group_workers == ("csd/0", "csd/2", "host/0")
    assert s.plan().imbalance_steps() == 0


def test_worker_joined_after_loss_gets_fresh_label():
    s = _session(n_csds=3)
    s.apply(WorkerLost(["csd/1"]))
    s.apply(WorkerJoined("csd", 1))
    workers = s.tune().group_workers
    # survivors keep their identities; the joiner gets a never-used index,
    # so the dead worker's (gone) private shard is never re-pinned
    assert "csd/1" not in workers and "csd/3" in workers
    assert s.fleet.by_name("csd").count == 3


def test_worker_joined_never_recycles_highest_dead_index():
    s = _session(n_csds=3)
    s.apply(WorkerLost(["csd/2"]))       # the HIGHEST index dies
    s.apply(WorkerJoined("csd", 1))
    workers = s.tune().group_workers
    # the joiner must not be relabeled as the dead csd/2
    assert "csd/2" not in workers and "csd/3" in workers


def test_drift_preserves_dataset_cursors():
    s = _session()
    ds = s.dataset
    ds.next_batch()
    cursors = dict(ds._cursor)
    assert any(v > 0 for v in cursors.values())
    s.apply(DriftDetected())
    assert s.dataset is ds                     # same object, cursors intact
    assert ds._cursor == cursors
    assert ds.schedule is s.tune().schedule


def test_force_retune_after_loss_keeps_membership():
    s = _session(n_csds=3)
    s.apply(WorkerLost(["csd/1"]))
    s.tune(force=True)                         # explicit full re-tune
    assert s.tune().group_workers == ("csd/0", "csd/2", "host/0")
    # the surviving worker's private shard stays planned and placed
    placed = {a.shard_id for a in s.place().assignments}
    assert "private-csd/2" in placed


def test_join_after_override_gets_unique_labels():
    donor = _session(n_csds=2)
    tp = donor.tune()
    s = _session(n_csds=2)
    s.override("tune", tp)                     # external re-tuner hook
    s.apply(WorkerJoined("csd", 1))
    workers = s.tune().group_workers
    assert len(set(workers)) == len(workers)   # no duplicate labels
    assert "csd/2" in workers


def test_full_class_death_then_rejoin():
    s = _session(n_csds=1)
    s.tune()
    s.apply(WorkerLost(["csd/0"]))             # the whole csd class dies
    assert all(c.name != "csd" for c in s.fleet.classes)
    s.apply(WorkerJoined("csd", 1))            # replacement node arrives
    assert s.fleet.by_name("csd").count == 1
    workers = s.tune().group_workers
    assert "csd/1" in workers and "csd/0" not in workers
    assert s.plan().imbalance_steps() == 0


def test_force_retune_preserves_capacity_and_compiled_step():
    s = _session(steps=2)
    s.run()
    compiled = s.compile()
    count = s.compile_count
    max_local = s.tune().schedule.max_local
    s.tune(force=True)
    assert s.tune().schedule.max_local == max_local
    assert s.compile() is compiled             # shapes held: step survives
    assert s.compile_count == count


def test_config_edit_between_runs_takes_effect():
    s = _session(steps=2)
    r1 = s.run()
    s.config.base_lr = 123.0
    r2 = s.run()
    assert s.compile_count == 2                # config change rebuilds
    assert r2.history[0]["lr"] > r1.history[0]["lr"] * 100


def test_run_continuation_keeps_optimizer_and_lr_progress():
    s = _session(steps=3)
    r1 = s.run()
    r2 = s.run(r1.params, opt_state=r1.opt_state, steps=2)
    # the lr-schedule step counter lives in opt_state: warmup continues
    # monotonically across the two runs instead of replaying from step 0
    # (smoke batches < base_batch, so the Goyal ramp is strictly decreasing)
    lrs = [h["lr"] for h in r1.history] + [h["lr"] for h in r2.history]
    assert all(a > b for a, b in zip(lrs, lrs[1:])), lrs
    assert r2.history[0]["lr"] != r1.history[0]["lr"]


def test_worker_joined_rejects_nonpositive_count():
    with pytest.raises(ValueError):
        WorkerJoined("csd", 0)
    with pytest.raises(ValueError):
        WorkerJoined("csd", -1)


def test_plan_override_keeps_compiled_step():
    s = _session(steps=2)
    s.run()
    compiled = s.compile()
    s.override("plan", s.plan())          # rebalancer hook: shapes untouched
    assert s.compile() is compiled
    assert s.compile_count == 1


def test_drift_keeps_dataset_consistent_with_placement():
    from repro.storage import manifest_sources

    s = _session(n_csds=3)
    _ = s.dataset
    s.apply(DriftDetected())
    # the live iterator must sample exactly what place() says it samples
    expected = manifest_sources(s.place(), list(s.tune().group_workers))
    assert s.dataset.group_sources == expected


def test_worker_lost_then_run_recompiles_once():
    s = _session(n_csds=3, steps=2)
    s.run()
    count = s.compile_count
    res = s.apply(WorkerLost(["csd/0"]))
    assert res.recompiled                      # group count changed: expected
    report = s.run(steps=2)
    assert np.isfinite(report.final_loss)
    assert s.compile_count == count + 1


# ---------------------------------------------------------------------------
# callbacks
# ---------------------------------------------------------------------------


def test_callback_registry_fires_typed_hooks():
    cb = CallbackRegistry()
    seen = {"steps": [], "retunes": [], "fleet": []}
    cb.on_step(lambda i, m: seen["steps"].append(i))
    cb.on_retune(lambda e, tp: seen["retunes"].append(e))
    cb.on_fleet_change(lambda e, r: seen["fleet"].append(e))

    s = _session(n_csds=3, steps=2, callbacks=cb)
    s.run()
    assert seen["steps"] == [0, 1]
    s.apply(DriftDetected())
    assert len(seen["retunes"]) == 1 and not seen["fleet"]
    s.apply(WorkerLost(["csd/2"]))
    assert len(seen["fleet"]) == 1 and isinstance(seen["fleet"][0], WorkerLost)


# ---------------------------------------------------------------------------
# FleetSpec
# ---------------------------------------------------------------------------


def test_fleetspec_demo_and_shards():
    spec = FleetSpec.demo(3)
    fleet = spec.build()
    assert fleet.by_name("host").count == 1
    assert fleet.by_name("csd").count == 3
    shards = spec.shards(private_per_worker={"csd": 10}, public=100)
    priv = [sh for sh in shards if sh.private]
    assert [sh.owner for sh in priv] == ["csd/0", "csd/1", "csd/2"]
    assert sum(not sh.private for sh in shards) == 1


def test_fleetspec_paper_matches_topology_preset():
    from repro.core.topology import paper_fleet

    assert FleetSpec.paper(24, "nasnet").build() == paper_fleet(24, "nasnet")


def test_fleetspec_immutable_builder():
    base = FleetSpec.custom("x").add("a", 1, 1.0, 1, 4, active_power=1.0)
    grown = base.add("b", 2, 2.0, 1, 4, active_power=1.0)
    assert len(base.classes) == 1 and len(grown.classes) == 2
    with pytest.raises(ValueError):
        FleetSpec.custom("empty").build()


# ---------------------------------------------------------------------------
# fleet-aware placement manifest
# ---------------------------------------------------------------------------


def test_place_returns_fleet_manifest():
    from repro.core.privacy import PlacementManifest
    from repro.storage import FleetManifest

    s = _session(n_csds=2)
    m = s.place()
    assert isinstance(m, FleetManifest)
    assert isinstance(m, PlacementManifest)      # the core surface survives
    assert m.backend == "synthetic"
    workers = {d.worker for d in m.devices}
    assert workers == set(s.tune().group_workers)
    # every device's custody covers its own private shard
    for sh in s.shards:
        if sh.private:
            rec = m.device_for(sh.owner)
            assert rec is not None and sh.shard_id in rec.custody


def test_worker_lost_manifest_reflects_quarantine():
    s = _session(n_csds=3)
    s.place()
    s.apply(WorkerLost(["csd/1"]))
    m = s.place()
    assert "private-csd/1" in m.quarantined
    assert m.device_for("csd/1") is None
    # no assignment may reference the dead worker or its shard
    assert all(a.worker != "csd/1" for a in m.assignments)
    assert all(a.shard_id != "private-csd/1" for a in m.assignments)


def test_attention_paths_count_layers_on_cpu(monkeypatch):
    """The compiled step and the report count each layer's attention path:
    on the CPU every layer of a scanned stack takes the chunked XLA path
    once the sequence passes the chunk threshold."""
    from repro.models import layers as L

    monkeypatch.setattr(L.FLAGS, "chunk_threshold", 32 * 32)
    s = _session(steps=1, seq_len=48)
    rep = s.run()
    n_layers = s.model.cfg.n_layers
    assert s.model.cfg.scan_layers and n_layers > 1
    assert rep.attention_paths == {"chunked": n_layers}
    assert s.compile().attention_paths == {"chunked": n_layers}


def _moe_session(steps=2, **model_kw):
    cfg = smoke_config("qwen3-moe-30b-a3b").with_(**model_kw)
    spec = FleetSpec.demo(1)
    return Session(
        model=get_model(cfg),
        optimizer=adamw(),
        fleet=spec,
        data=DataConfig(vocab=cfg.vocab, seq_len=16),
        shards=spec.shards(private_per_worker={"csd": 64}, public=4096),
        config=SessionConfig(total_steps=steps),
    )


def test_moe_run_counts_expert_rows_in_one_more_read():
    """A dropless MoE step reports its expert rows and the layers that took
    the static buffer: Session.run adds them, and the layers it ran, to the
    process-wide counters with one read more than a dense step's."""
    from repro.api.spans import counters

    s = _moe_session(steps=2)
    cfg = s.model.cfg
    before = counters()
    rep = s.run()
    after = counters()
    grown = {k: after[k] - before.get(k, 0)
             for k in ("moe_rows_routed", "moe_rows_computed",
                       "moe_full_buffer_layers", "moe_layer_steps")}
    rows = s.tune().schedule.global_rows
    # every expert is held: each token's k copies are routed here, per layer
    assert grown["moe_rows_routed"] == (
        2 * cfg.n_layers * rows * 16 * cfg.experts_per_token)
    assert grown["moe_rows_computed"] >= grown["moe_rows_routed"]
    # every layer of both steps counted; all held, so C is the whole buffer
    # and no layer falls back to it
    assert grown["moe_layer_steps"] == 2 * cfg.n_layers
    assert grown["moe_full_buffer_layers"] == 0
    assert rep.readbacks == 7 * rep.steps_run
    assert all("moe_rows" not in h for h in rep.history)


@pytest.mark.parametrize("weight", [0.001, 0.5])
def test_moe_aux_weight_comes_from_the_model_config(weight):
    s = _moe_session(steps=1, router_aux_weight=weight)
    h = s.run().history[0]
    assert h["aux"] > 0
    np.testing.assert_allclose(h["total"], h["loss"] + weight * h["aux"],
                               rtol=1e-6)
