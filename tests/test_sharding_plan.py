"""The sharding rule engine and the staged ShardingPlan.

Covers: the `spec_for` no-duplicate-mesh-axis invariant (property-tested),
`_divisible_spec` fallbacks (uneven heads, small meshes), the ShardingPlan
artifact (structure, caching, elastic invalidation, `compile_count` probe),
sharded init (params born on the mesh, never host-replicated), sharded
checkpoint restore, and the `with_logical_constraint` warn-once contract.
"""
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from jax.sharding import NamedSharding, PartitionSpec as P

from repro.api import (
    DriftDetected, FleetSpec, Session, SessionConfig, ShardingPlan, WorkerLost,
)
from repro.configs import smoke_config
from repro.distributed.sharding import (
    _divisible_spec, get_rules, make_rules, spec_for, use_rules,
    with_logical_constraint,
)
from repro.models.api import get_model
from repro.optim import adamw, sgd_momentum
from repro.storage import DataConfig
from repro.train.steps import (
    BATCH_AXES, abstract_batch, abstract_train_state, build_sharding_plan,
)

from _hypothesis_compat import given, settings, st

# every logical axis name any rule table knows about, plus unknowns
_LOGICAL = sorted(make_rules(fsdp=True, seq_shard=True)) + ["unknown", None]


def _flat_axes(spec: P):
    flat = []
    for part in spec:
        if isinstance(part, tuple):
            flat.extend(part)
        elif part is not None:
            flat.append(part)
    return flat


# ---------------------------------------------------------------------------
# rule engine: spec_for never assigns one mesh axis to two dims of a leaf
# ---------------------------------------------------------------------------


@settings(max_examples=200, deadline=None)
@given(
    axes=st.lists(st.sampled_from(_LOGICAL), min_size=1, max_size=6),
    fsdp=st.booleans(),
    seq_shard=st.booleans(),
)
def test_spec_for_never_duplicates_mesh_axis(axes, fsdp, seq_shard):
    """For ANY logical-axis tuple under ANY stock rule table, a mesh axis
    appears at most once in the resulting PartitionSpec (XLA rejects specs
    that shard two dims of one tensor over the same mesh axis)."""
    rules = make_rules(fsdp=fsdp, seq_shard=seq_shard)
    spec = spec_for(tuple(axes), rules)
    flat = _flat_axes(spec)
    assert len(flat) == len(set(flat)), (axes, spec)
    assert len(spec) <= len(axes)          # never longer than the leaf rank


def test_spec_for_duplicate_logical_axes_keep_first():
    """Same logical name twice (e.g. a square (embed, embed) weight): the
    first dim takes the mesh axis, the second replicates."""
    rules = make_rules(fsdp=True)
    spec = spec_for(("embed", "embed"), rules)
    flat = _flat_axes(spec)
    assert len(flat) == len(set(flat))
    assert spec[0] == "data"


# ---------------------------------------------------------------------------
# _divisible_spec fallbacks
# ---------------------------------------------------------------------------


class _FakeMesh:
    def __init__(self, **shape):
        self.shape = shape


def test_divisible_spec_uneven_heads_fall_back_replicated():
    # 56 query heads on a 16-way model axis: 56 % 16 != 0 -> that dim
    # replicates (the memory is carried by the other sharded dims)
    mesh = _FakeMesh(data=16, model=16)
    s = _divisible_spec(P(None, "model", None), (4, 56, 128), mesh)
    assert s == P()
    # 64 heads divide: the axis survives
    assert _divisible_spec(P(None, "model", None), (4, 64, 128), mesh) == P(None, "model")


def test_divisible_spec_small_mesh_drops_absent_axes():
    # batch rows shard over ("pod", "data"); a single-pod host mesh has no
    # "pod" axis -> only "data" survives (and only if it divides)
    mesh = _FakeMesh(data=4, model=1)
    assert _divisible_spec(P(("pod", "data"), None), (8, 16), mesh) == P("data")
    assert _divisible_spec(P(("pod", "data"), None), (6, 16), mesh) == P()


def test_divisible_spec_partial_tuple_keeps_divisible_prefix():
    # (pod=2, data=8): 8 rows fit pod*? -> pod kept (8%2==0), then data
    # needs 2*8=16 | 8 -> dropped; single-axis remainder collapses to str
    mesh = _FakeMesh(pod=2, data=8)
    assert _divisible_spec(P(("pod", "data"),), (8,), mesh) == P("pod")


def test_divisible_spec_rank_overflow_is_replicated():
    # spec longer than the shape: excess dims replicate instead of erroring
    mesh = _FakeMesh(data=2)
    assert _divisible_spec(P("data", "data"), (4,), mesh) == P("data")


# ---------------------------------------------------------------------------
# ShardingPlan: structure, caching, elastic invalidation
# ---------------------------------------------------------------------------


def _session(n_csds=2, steps=2, optimizer=None, spec=None):
    cfg = smoke_config("deepseek-7b")
    spec = spec or FleetSpec.demo(n_csds)
    return Session(
        model=get_model(cfg),
        optimizer=optimizer or adamw(),
        fleet=spec,
        data=DataConfig(vocab=cfg.vocab, seq_len=16),
        shards=spec.shards(private_per_worker={"csd": 64}, public=4096),
        config=SessionConfig(total_steps=steps),
    )


def test_plan_structure_matches_state():
    s = _session()
    plan = s.shard()
    assert isinstance(plan, ShardingPlan)
    params_abs, _, opt_abs = abstract_train_state(s.model, s.optimizer)
    assert (jax.tree_util.tree_structure(plan.params)
            == jax.tree_util.tree_structure(params_abs))
    assert (jax.tree_util.tree_structure(plan.opt)
            == jax.tree_util.tree_structure(opt_abs))
    assert set(plan.batch) == set(BATCH_AXES) == set(
        abstract_batch(4, 8)
    )
    # every leaf is a NamedSharding on the plan's mesh
    for leaf in jax.tree_util.tree_leaves(plan.params):
        assert isinstance(leaf, NamedSharding) and leaf.mesh == plan.mesh
    # batch rows shard over "data"; the step counter is replicated
    assert "data" in _flat_axes(plan.batch["tokens"].spec)
    assert plan.opt.step.spec == P()


def test_plan_sgd_opt_state_has_no_nu():
    s = _session(optimizer=sgd_momentum())
    plan = s.shard()
    assert plan.opt.nu is None
    _, opt_state = s.init_state(plan)
    assert opt_state.nu is None


def test_plan_cached_and_kept_across_drift():
    s = _session()
    s.run()
    plan = s.shard()
    assert s.shard() is plan                   # memoized
    count = s.compile_count
    s.apply(DriftDetected())
    assert s.shard() is plan                   # rows pinned: plan survives
    assert s.compile_count == count            # and so does the step
    s.tune(force=True)
    assert s.shard() is plan


def test_plan_rederived_on_elastic_resize():
    s = _session(n_csds=3)
    plan = s.shard()
    rows = plan.global_rows
    s.apply(WorkerLost(["csd/1"]))
    plan2 = s.shard()
    assert plan2 is not plan                   # mesh resized: re-derived
    assert plan2.global_rows == s.tune().schedule.global_rows != rows


def test_compile_is_sharding_explicit():
    s = _session()
    compiled = s.compile()
    plan = s.shard()
    assert compiled.in_shardings == (plan.params, plan.opt, plan.batch)
    assert compiled.out_shardings == (plan.params, plan.opt, plan.replicated)


def test_fleetspec_sharding_overrides_reach_plan():
    spec = FleetSpec.demo(2).with_sharding(vocab=None)
    s = _session(spec=spec)
    plan = s.shard()
    assert plan.rules["vocab"] is None
    # default rules shard vocab over "model"
    assert make_rules()["vocab"] == "model"
    # overrides merge, later calls win
    spec2 = spec.with_sharding(vocab="model")
    assert dict(spec2.sharding)["vocab"] == "model"


# ---------------------------------------------------------------------------
# sharded init: params are born on the mesh with the plan's shardings
# ---------------------------------------------------------------------------


def test_init_state_places_leaves_on_plan():
    s = _session()
    plan = s.shard()
    params, opt_state = s.init_state(plan)
    flat_p = jax.tree_util.tree_leaves(params)
    flat_sh = jax.tree_util.tree_leaves(plan.params)
    assert len(flat_p) == len(flat_sh)
    for leaf, sh in zip(flat_p, flat_sh):
        assert leaf.sharding.is_equivalent_to(sh, leaf.ndim)
    assert int(opt_state.step) == 0
    # the same init is what run() trains from
    report = s.run()
    for leaf, sh in zip(jax.tree_util.tree_leaves(report.params), flat_sh):
        assert leaf.sharding.is_equivalent_to(sh, leaf.ndim)


def test_run_rehomes_caller_state_onto_plan(tmp_path):
    s = _session(steps=2)
    r1 = s.run()
    # host-side numpy state (e.g. loaded out-of-band) is adopted onto the plan
    host_params = jax.tree_util.tree_map(np.asarray, r1.params)
    r2 = s.run(host_params, opt_state=r1.opt_state, steps=1)
    assert np.isfinite(r2.final_loss)


def test_checkpoint_restore_lands_on_plan(tmp_path):
    cfg_dir = str(tmp_path)
    s = _session(steps=2)
    s.config.checkpoint_dir = cfg_dir
    s.config.checkpoint_every = 2
    s.config.async_checkpoint = False
    s.run()
    s2 = _session(steps=4)
    s2.config.checkpoint_dir = cfg_dir
    s2.config.checkpoint_every = 10
    report = s2.run()
    assert report.start_step == 2              # resumed from the checkpoint
    plan = s2.shard()
    for leaf, sh in zip(jax.tree_util.tree_leaves(report.params),
                        jax.tree_util.tree_leaves(plan.params)):
        assert leaf.sharding.is_equivalent_to(sh, leaf.ndim)


def test_use_rules_installs_and_restores():
    """compile() traces the step under the plan's rule table; the context
    must restore the previous table (and constrain flag) afterwards."""
    before = get_rules()
    override = make_rules(extra={"vocab": None})
    with use_rules(override):
        assert get_rules() is override
        assert get_rules()["vocab"] is None
    assert get_rules() is before


# ---------------------------------------------------------------------------
# with_logical_constraint: expected failures warn ONCE, typos are not silent
# ---------------------------------------------------------------------------


def test_constraint_mismatch_warns_once():
    from repro.distributed.sharding import reset_constraint_warnings
    from repro.launch.mesh import make_single_mesh

    # the cache is process-global: clear it so the ONE warning asserted
    # below is observed regardless of which test tripped this key earlier
    reset_constraint_warnings()
    mesh = make_single_mesh()
    x = jnp.zeros((4,))
    with jax.set_mesh(mesh):
        with warnings.catch_warnings(record=True) as w:
            warnings.simplefilter("always")
            # rank-mismatched constraint (2 sharded parts on a 1-D array):
            # expected ValueError -> identity + ONE RuntimeWarning
            y = with_logical_constraint(x, "batch", "heads")
            np.testing.assert_array_equal(np.asarray(y), np.asarray(x))
            assert len([r for r in w if r.category is RuntimeWarning]) == 1
            with_logical_constraint(x, "batch", "heads")
            assert len([r for r in w if r.category is RuntimeWarning]) == 1
    # a well-formed constraint still applies silently
    with jax.set_mesh(mesh):
        with warnings.catch_warnings(record=True) as w:
            warnings.simplefilter("always")
            with_logical_constraint(jnp.zeros((4, 4)), "batch", None)
            assert not [r for r in w if r.category is RuntimeWarning]
