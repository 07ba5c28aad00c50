"""`Session`: the staged public entry point for the whole Stannis pipeline.

The paper's pipeline is tune -> balance -> place -> train (Algorithm 1,
Eq. 1, privacy placement).  The seed ``Trainer`` fused all four into one
opaque ``setup()``; a ``Session`` decomposes them into explicit, frozen,
cached, individually overridable stage artifacts:

    session = Session(model=model, optimizer=adamw(),
                      fleet=FleetSpec.demo(2), data=DataConfig(...),
                      shards=spec.shards(...), config=SessionConfig(...))
    tune_plan = session.tune()      # Algorithm 1 -> TunePlan
    epoch     = session.plan()      # Eq. 1       -> EpochPlan
    manifest  = session.place()     # privacy     -> FleetManifest (device-aware)
    shard     = session.shard()     # rule table x mesh -> ShardingPlan
    step      = session.compile()   # jitted SPMD -> CompiledStep
    report    = session.run()       # training    -> TrainReport

Execution is *sharding-explicit*: ``shard()`` resolves the logical-axis rule
table (:mod:`repro.distributed.sharding`) against the live mesh once into a
:class:`~repro.api.artifacts.ShardingPlan`; ``compile()`` jits the step with
the plan as explicit ``in_shardings``/``out_shardings``; model init is
jitted with ``out_shardings`` so parameters are BORN as mesh shards (a full
replicated param tree never exists on host); the meshfeed backend lands
batches with the plan's layout; and checkpoint restore places leaves
straight onto the plan's shardings for whatever mesh shape the restart has.
The plan is keyed by the pinned row capacity, so drift re-tunes keep both
the plan and the compiled step (the ``compile_count`` probe still holds),
while a node loss/join resizes the mesh and re-derives both.

The data plane is the :mod:`repro.storage` device fleet: ``session.devices``
is a :class:`~repro.storage.DeviceFleet` (one StorageDevice per dp-group
worker, backend chosen by ``StorageSpec`` / ``FleetSpec.with_storage``), and
``run()`` pulls every batch through it — each group's rows are assembled in
its own device, and elastic events re-home custody through the fleet API
(WorkerLost quarantines the dead device's private shards and re-homes its
public custody; WorkerJoined provisions a fresh device).

Stages are lazy and memoized: calling ``run()`` directly executes the whole
chain; calling a stage twice returns the SAME artifact object.  A stage can
be overridden (``session.override("tune", my_plan)``), which invalidates
everything downstream of it — that is the hook online re-tuners and elastic
schedulers build on.

All mid-run fleet changes go through ONE replanning path,
:meth:`Session.apply`:

    session.apply(WorkerLost(["csd/1"]))   # paper's backfill remedy
    session.apply(WorkerJoined("csd", 2))  # elastic growth
    session.apply(DriftDetected())         # online re-tune, zero recompile

``apply`` preserves the pinned row capacity across events, so a drift
re-tune keeps tensor shapes bit-identical (the compiled step is reused; the
``compile_count`` probe proves it), and a node loss keeps ``max_local``
stable so only the group dimension changes.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import use_abstract_mesh

from repro.api.artifacts import (
    CompiledStep, ReplanResult, ShardingPlan, TrainReport, TunePlan,
)
from repro.api.callbacks import CallbackRegistry
from repro.api.events import DriftDetected, FleetEvent, WorkerJoined, WorkerLost
from repro.api.fleet import FleetSpec
from repro.api.spans import Span, count
from repro.checkpoint.manager import CheckpointManager, ClusterCheckpointManager
from repro.core.hetero import BatchSchedule, schedule_from_tune
from repro.core.load_balance import EpochPlan, plan_epoch
from repro.core.privacy import PlacementManifest, Shard, place
from repro.core.topology import ClusterSpec, Fleet, ProcessMap
from repro.core.tuner import BenchmarkFn, DriftMonitor, tune
from repro.models.api import Model
from repro.storage import (
    DataConfig, DeviceFleet, FleetBatcher, FleetManifest, StorageSpec,
    make_fleet_batcher, manifest_sources,
)
from repro.distributed.sharding import use_rules
from repro.models.layers import attention_path_tally
from repro.launch.mesh import ClusterContext, make_single_mesh
from repro.optim.optimizers import Optimizer
from repro.optim.schedules import goyal_schedule
from repro.train.steps import (
    abstract_train_state, build_sharding_plan, make_bucketed_apply_step,
    make_bucketed_grad_step, make_train_step, plan_buckets,
)

PyTree = Any

# stage dependency graph: invalidating a stage clears it plus everything
# that derives from it.  Note "shard"/"compile" depend only on the tune
# schedule (shapes + mesh + lr anchor) — a plan/place override must not
# throw away the sharding plan or the jitted step.
_STAGES = ("tune", "plan", "place", "dataset", "shard", "compile")
_DOWNSTREAM = {
    "tune": ("plan", "place", "dataset", "shard", "compile"),
    "plan": ("place", "dataset"),
    "place": ("dataset",),
    "dataset": (),
    "shard": ("compile",),
    "compile": (),
}


def _tallying(fn: Callable, paths: Dict[str, int]) -> Callable:
    """``fn``, leaving in ``paths`` the attention paths its latest trace took."""
    def traced(*args):
        with attention_path_tally() as tally:
            out = fn(*args)
        paths.clear()
        paths.update(tally)
        return out
    return traced


@dataclasses.dataclass
class SessionConfig:
    """Run-level knobs (training length, LR rule, checkpointing, drift).

    Mutable by design (unlike the stage artifacts): callers tweak e.g.
    ``total_steps`` or ``retune_margin`` between runs of the same session.
    """

    total_steps: int = 100
    base_lr: float = 1e-3
    base_batch: int = 256
    warmup_steps: int = 20
    checkpoint_dir: Optional[str] = None
    checkpoint_every: int = 50
    keep_checkpoints: int = 3
    async_checkpoint: bool = True
    retune_margin: float = 0.2       # DriftMonitor threshold = tuner 1/E
    retune_patience: int = 10
    log_every: int = 10
    seed: int = 0


class Session:
    """Staged pipeline: tune -> plan -> place -> shard -> compile -> run."""

    def __init__(
        self,
        *,
        model: Model,
        optimizer: Optimizer,
        fleet: Union[Fleet, FleetSpec],
        data: DataConfig,
        shards: Sequence[Shard],
        config: Optional[SessionConfig] = None,
        benchmark: Optional[BenchmarkFn] = None,
        callbacks: Optional[CallbackRegistry] = None,
        storage: Optional[StorageSpec] = None,
    ):
        self.model = model
        self.optimizer = optimizer
        spec_storage = fleet.storage if isinstance(fleet, FleetSpec) else None
        # fleet-wide logical-axis rule overrides (FleetSpec.with_sharding)
        self.sharding_overrides: Dict[str, Any] = (
            dict(fleet.sharding) if isinstance(fleet, FleetSpec) else {}
        )
        self.fleet: Fleet = fleet.build() if isinstance(fleet, FleetSpec) else fleet
        self.data = data
        self._shards: List[Shard] = list(shards)
        self.config = config or SessionConfig()
        self.benchmark = benchmark
        self.callbacks = callbacks or CallbackRegistry()
        # the storage data plane: explicit arg > FleetSpec.storage > default
        self.storage: StorageSpec = storage or spec_storage or StorageSpec()
        # cluster mode: the spec travels on the FleetSpec; the live process
        # identity (ClusterContext) is attached by the WorkerRuntime after
        # the jax.distributed handshake.  No context attached = one
        # process: same stages.
        self.cluster_spec: Optional[ClusterSpec] = (
            fleet.cluster if isinstance(fleet, FleetSpec) else None
        )
        self._cluster: Optional[ClusterContext] = None
        self._local_plan: Optional[ShardingPlan] = None
        # the device fleet persists across stage rebuilds — custody state
        # (quarantine tombstones, re-homed public shards) must survive
        # re-plans exactly like live membership does
        self._device_fleet: Optional[DeviceFleet] = None
        self._artifacts: Dict[str, Any] = {}
        self._compile_count = 0
        # WorkerClass templates survive a fully-dead class leaving the fleet,
        # so a replacement node can still rejoin under the same class name
        self._class_templates: Dict[str, Any] = {
            c.name: c for c in self.fleet.classes
        }
        # canonical live membership: survives stage rebuilds (tune(force=True)
        # must not resurrect dead workers from bare class counts)
        self._group_workers: Optional[Tuple[str, ...]] = None
        # per-class high-water mark of worker indices ever handed out, so a
        # joiner can never be relabeled as a dead worker
        self._next_index: Dict[str, int] = {}

    def _note_labels(self, workers: Sequence[str]) -> None:
        for w in workers:
            cls, idx = w.rsplit("/", 1)
            self._next_index[cls] = max(
                self._next_index.get(cls, 0), int(idx) + 1
            )

    # -- cluster mode ------------------------------------------------------

    @property
    def cluster(self) -> Optional[ClusterContext]:
        return self._cluster

    def attach_cluster(self, ctx: ClusterContext) -> None:
        """Bind this session to its worker-process identity (see
        :class:`~repro.launch.mesh.ClusterContext`).  Must happen before the
        first stage builds — custody and mesh resolution key off it."""
        if self._artifacts or self._device_fleet is not None:
            raise RuntimeError(
                "attach_cluster() must run before any stage is built"
            )
        if self.storage.backend not in ("meshfeed",):
            raise ValueError(
                f"cluster execution needs a mesh-delivery storage backend, "
                f"not {self.storage.backend!r} (use "
                f"FleetSpec.with_cluster / with_storage('meshfeed'))"
            )
        self._cluster = ctx

    def _is_cluster(self) -> bool:
        return self._cluster is not None and self._cluster.n_processes > 1

    def process_map(self) -> Optional[ProcessMap]:
        """dp-group -> process custody (None outside cluster mode)."""
        if not self._is_cluster():
            return None
        tp = self.tune()
        pmap = ProcessMap(tp.group_workers, self._cluster.n_processes)
        if pmap.n_groups % pmap.n_processes != 0:
            raise ValueError(
                f"{pmap.n_groups} dp-groups do not split evenly over "
                f"{pmap.n_processes} processes — the mesh's equal row slabs "
                f"would straddle process custody; size the fleet so "
                f"groups % processes == 0"
            )
        return pmap

    def _exec_plan(self) -> ShardingPlan:
        """The plan the STEP runs on: the local (hostsync) compute plan in
        a cluster whose backend cannot span processes, the global plan
        everywhere else.  State (init, restore, adoption) follows it."""
        plan = self.shard()
        if self._is_cluster() and self._cluster.mode == "hostsync":
            return self._local_plan
        return plan

    # -- introspection -----------------------------------------------------

    @property
    def shards(self) -> Tuple[Shard, ...]:
        """Live shard set (shrinks when an owner dies — privacy constraint)."""
        return tuple(self._shards)

    @property
    def compile_count(self) -> int:
        """How many times a CompiledStep was built (the no-recompile probe)."""
        return self._compile_count

    @property
    def devices(self) -> DeviceFleet:
        """The live storage device fleet (provisioned on first access).
        In cluster mode only THIS process's dp-groups get real devices —
        every other worker is a remote custody record."""
        if self._device_fleet is None:
            tp = self.tune()
            pmap = self.process_map()
            self._device_fleet = DeviceFleet.provision(
                tp.group_workers, self._shards, self.data, spec=self.storage,
                process_map=pmap,
                process_id=self._cluster.process_id if pmap else 0,
            )
        return self._device_fleet

    def cached(self, stage: str) -> bool:
        return stage in self._artifacts

    def override(self, stage: str, artifact: Any) -> None:
        """Install a caller-supplied artifact for ``stage``; downstream stages
        are invalidated and will rebuild against it on next access."""
        if stage not in _STAGES:
            raise KeyError(f"unknown stage {stage!r}; stages are {_STAGES}")
        self._invalidate(stage)
        self._artifacts[stage] = artifact
        if stage == "tune":
            # an externally supplied TunePlan defines the live membership
            self._group_workers = tuple(artifact.group_workers)
            self._note_labels(artifact.group_workers)

    def _invalidate(self, from_stage: str) -> None:
        self._artifacts.pop(from_stage, None)
        for s in _DOWNSTREAM[from_stage]:
            self._artifacts.pop(s, None)

    # -- stage 1: Algorithm 1 ---------------------------------------------

    def tune(self, *, force: bool = False) -> TunePlan:
        prev = self._artifacts.get("tune")
        prev_compiled = self._artifacts.get("compile")
        prev_shard = self._artifacts.get("shard")
        if force:
            self._invalidate("tune")
        if "tune" not in self._artifacts:
            result = tune(self.fleet, self.benchmark)
            if self._group_workers is None:
                # first tune: physical workers are enumerated from class counts
                class_counts = {c.name: c.count for c in self.fleet.classes}
                schedule, workers = schedule_from_tune(
                    result.batches, class_counts
                )
                self._group_workers = tuple(workers)
            else:
                # rebuild (e.g. force=True after elastic events): keep the
                # live membership, map per-class batches onto it
                workers = self._group_workers
                new_batches = tuple(
                    result.batches[w.rsplit("/", 1)[0]] for w in workers
                )
                if prev is not None and prev.group_workers == workers:
                    # preserve the pinned capacity (and round_to): a re-tune
                    # that fits under it keeps the compiled shapes
                    schedule = prev.schedule.with_batches(new_batches)
                else:
                    schedule = BatchSchedule(new_batches)
            self._note_labels(workers)
            self._artifacts["tune"] = TunePlan(
                result=result, schedule=schedule, group_workers=tuple(workers)
            )
            if (
                prev_shard is not None
                and prev_shard.global_rows == schedule.global_rows
            ):
                # same rows => same mesh => the resolved plan survives
                self._artifacts["shard"] = prev_shard
            if (
                prev_compiled is not None
                and prev_compiled.global_rows == schedule.global_rows
            ):
                self._artifacts["compile"] = prev_compiled
        return self._artifacts["tune"]

    # -- stage 2: Eq. 1 epoch balancing -----------------------------------

    def plan(self, *, force: bool = False) -> EpochPlan:
        if force:
            self._invalidate("plan")
        if "plan" not in self._artifacts:
            tp = self.tune()
            batches = dict(zip(tp.group_workers, tp.schedule.group_batches))
            private_sizes = {w: 0 for w in tp.group_workers}
            n_public = 0
            for s in self._shards:
                if s.private:
                    private_sizes[s.owner] = (
                        private_sizes.get(s.owner, 0) + s.n_samples
                    )
                else:
                    n_public += s.n_samples
            self._artifacts["plan"] = plan_epoch(batches, private_sizes, n_public)
        return self._artifacts["plan"]

    # -- stage 3: privacy placement ---------------------------------------

    def place(self, *, force: bool = False) -> FleetManifest:
        """Privacy placement, fleet-aware: the core manifest wrapped with
        per-device custody records (which device holds which shards, under
        which backend)."""
        if force:
            self._invalidate("place")
        if "place" not in self._artifacts:
            epoch = self.plan()
            targets = {sh.worker: sh.total for sh in epoch.shares}
            core = place(list(self._shards), targets)
            self._artifacts["place"] = self.devices.manifest(core)
        return self._artifacts["place"]

    # -- stage 3b: data pipeline (internal, derived from plan + place) -----

    @property
    def dataset(self) -> FleetBatcher:
        if "dataset" not in self._artifacts:
            tp = self.tune()
            self._artifacts["dataset"] = make_fleet_batcher(
                self.data, tp.schedule, list(tp.group_workers),
                self.place(), self.devices,
            )
        return self._artifacts["dataset"]

    # -- stage 4: the sharding plan ---------------------------------------

    def shard(self, *, force: bool = False) -> ShardingPlan:
        """Resolve the logical-axis rule table against the live mesh ONCE.

        The plan is the placement contract every downstream consumer reads:
        ``compile()`` (explicit in/out_shardings), sharded init, the
        meshfeed data plane, and checkpoint restore.  It is keyed by the
        schedule's ``global_rows``: a cached plan for a different row count
        (an elastic resize changed the mesh) is invalidated and re-derived,
        together with the compiled step.
        """
        if force:
            self._invalidate("shard")
        tp = self.tune()
        cached = self._artifacts.get("shard")
        if cached is not None and cached.global_rows != tp.schedule.global_rows:
            self._invalidate("shard")      # elastic mesh resize: re-derive
        if "shard" not in self._artifacts:
            rows = tp.schedule.global_rows
            if self._is_cluster():
                # the CLUSTER mesh: every process's devices, process-major,
                # resolved identically in every process (the shared
                # contract each worker feeds its addressable slice of)
                mesh = self._cluster.global_mesh(rows)
            else:
                mesh = self.devices.feed_mesh(rows)
            if mesh is None:
                # host-delivery backends: same code path on a 1x1 mesh
                mesh = make_single_mesh()
            self._artifacts["shard"] = build_sharding_plan(
                self.model, self.optimizer,
                mesh=mesh,
                global_rows=rows,
                seq_len=self.data.seq_len,
                extra_rules=self.sharding_overrides or None,
            )
            self._local_plan = None
        plan = self._artifacts["shard"]
        if (
            self._is_cluster()
            and self._cluster.mode == "hostsync"
            and self._local_plan is None
        ):
            # the hostsync COMPUTE plan: this process's row slab on its own
            # devices, chunked exactly like its share of the global mesh so
            # the local view reuses the global feed's buffers
            pmap = self.process_map()
            start, stop = pmap.row_span(
                self._cluster.process_id, tp.schedule.max_local
            )
            self._local_plan = build_sharding_plan(
                self.model, self.optimizer,
                mesh=self._cluster.local_mesh(
                    stop - start,
                    data_axis=plan.data_axis // self._cluster.n_processes,
                ),
                global_rows=stop - start,
                seq_len=self.data.seq_len,
                extra_rules=self.sharding_overrides or None,
            )
        # (re-)hand the plan to the data plane: meshfeed lands every batch
        # with the plan's exact NamedShardings; idempotent for other backends
        self.devices.adopt_plan(
            plan,
            self._local_plan
            if self._is_cluster() and self._cluster.mode == "hostsync"
            else None,
        )
        return plan

    # -- stage 5: the jitted SPMD step ------------------------------------

    def _config_key(self) -> Tuple:
        """The SessionConfig values baked into the compiled step."""
        cfg = self.config
        return (cfg.base_lr, cfg.base_batch, cfg.warmup_steps,
                cfg.total_steps)

    def compile(self, *, force: bool = False) -> CompiledStep:
        if force:
            self._invalidate("compile")
        cached = self._artifacts.get("compile")
        if cached is not None and cached.config_key != self._config_key():
            # config edits between runs must take effect (the step bakes in
            # the lr schedule); drift re-tunes deliberately do NOT count —
            # valid_rows stays anchored at build time, as in the seed
            self._invalidate("compile")
        if "compile" not in self._artifacts:
            tp = self.tune()
            plan = self.shard()
            sched = goyal_schedule(
                self.config.base_lr,
                tp.schedule.valid_rows,
                base_batch=self.config.base_batch,
                warmup_steps=self.config.warmup_steps,
                total_steps=self.config.total_steps,
            )
            paths: Dict[str, int] = {}
            if self._is_cluster() and self._cluster.mode == "hostsync":
                step_fn, in_sh, out_sh = self._compile_hostsync(sched, paths)
            else:
                step = make_train_step(self.model, self.optimizer, sched)
                mesh = plan.mesh

                def step_in_mesh(params, opt_state, batch):
                    # trace under the plan's mesh AND rule table so the
                    # model's logical-axis activation constraints resolve
                    # against the same (possibly overridden) rules that
                    # produced the argument shardings — not the defaults
                    with use_rules(plan.rules), use_abstract_mesh(mesh.abstract_mesh):
                        return step(params, opt_state, batch)

                step_in_mesh = _tallying(step_in_mesh, paths)

                in_sh = (plan.params, plan.opt, plan.batch)
                # metrics are scalars: plan.replicated is a pytree-prefix
                # for the whole metrics dict
                out_sh = (plan.params, plan.opt, plan.replicated)
                step_fn = jax.jit(
                    step_in_mesh,
                    in_shardings=in_sh,
                    out_shardings=out_sh,
                    donate_argnums=(0, 1),
                )
            self._compile_count += 1
            self._artifacts["compile"] = CompiledStep(
                step_fn=step_fn,
                global_rows=tp.schedule.global_rows,
                seq_len=self.data.seq_len,
                valid_rows=tp.schedule.valid_rows,
                build_id=self._compile_count,
                config_key=self._config_key(),
                in_shardings=in_sh,
                out_shardings=out_sh,
                attention_paths=paths,
            )
        return self._artifacts["compile"]

    def _transport_spec(self):
        """The TransportSpec in force: the attached context's (set by the
        worker CLI) wins; the FleetSpec's ClusterSpec is the fallback."""
        from repro.core.topology import TransportSpec

        if self._cluster is not None and self._cluster.transport_spec is not None:
            return self._cluster.transport_spec
        if self.fleet.cluster is not None:
            return self.fleet.cluster.transport
        return TransportSpec()

    def _compile_hostsync(self, sched, paths: Dict[str, int]):
        """The cluster step for backends that cannot run cross-process XLA
        programs: a jitted partial-gradient half over this process's local
        plan emitting per-bucket flat f32 vectors, a
        :class:`~repro.launch.transport.GradReducer` round (compression /
        overlap / star-or-ring per the :class:`TransportSpec`), and a
        jitted apply half that unflattens inside the step — one ``step_fn``
        with the standard signature.  Numerically the single-program step
        (see :func:`make_partial_grad_step`); counts as ONE compile (the
        no-recompile probe spans both halves).
        """
        from repro.launch.transport import GradReducer, StarTransport

        lp = self._local_plan
        ctx = self._cluster
        tspec = self._transport_spec()
        params_abs, _ = self.model.init_params(abstract=True)
        groups = plan_buckets(params_abs, tspec.buckets)
        grad_step = make_bucketed_grad_step(self.model, groups)
        apply_step = make_bucketed_apply_step(
            self.optimizer, sched, params_abs, groups,
            aux_weight=self.model.cfg.router_aux_weight,
        )

        def grad_in_mesh(params, batch):
            with use_rules(lp.rules), use_abstract_mesh(lp.mesh.abstract_mesh):
                return grad_step(params, batch)

        grad_in_mesh = _tallying(grad_in_mesh, paths)

        def apply_in_mesh(params, opt_state, bucket_vecs, sums):
            with use_rules(lp.rules), use_abstract_mesh(lp.mesh.abstract_mesh):
                return apply_step(params, opt_state, bucket_vecs, sums)

        vec_sh = tuple(lp.replicated for _ in groups)
        jit_grad = jax.jit(
            grad_in_mesh,
            in_shardings=(lp.params, lp.batch),
            out_shardings=(vec_sh, lp.replicated),
        )
        # explicit in_shardings matter: the reduced buckets come back as
        # numpy arrays, and jit without placement hints pays a slow
        # host-layout probe on every call (measured ~60ms vs ~4ms/step)
        jit_apply = jax.jit(
            apply_in_mesh,
            in_shardings=(lp.params, lp.opt, vec_sh, lp.replicated),
            out_shardings=(lp.params, lp.opt, lp.replicated),
            donate_argnums=(0, 1),
        )
        reducer = None
        if ctx.sync is not None:
            # cached on the context so error-feedback residuals (and the
            # ring's sockets) survive recompiles
            reducer = ctx.grad_reducer
            if reducer is None:
                wire = ctx.transport or StarTransport(ctx.sync)
                reducer = GradReducer(
                    wire, tspec, ctx.process_id, ctx.n_processes
                )
                ctx.grad_reducer = reducer
        counter = iter(range(1 << 62))

        def step_fn(params, opt_state, batch):
            vecs, sums = jit_grad(params, batch)
            if reducer is not None:
                host_vecs = [np.asarray(jax.device_get(v)) for v in vecs]
                host_sums = jax.tree_util.tree_map(
                    lambda x: np.asarray(jax.device_get(x)), sums
                )
                # deterministic pid-ordered reduction: every process gets
                # identical totals, applies the identical update, and the
                # replicas stay bit-synchronized without a broadcast
                red_vecs, sums = reducer.reduce(
                    f"step/{next(counter)}", host_vecs, host_sums
                )
                vecs = tuple(red_vecs)
            return jit_apply(params, opt_state, vecs, sums)

        in_sh = (lp.params, lp.opt, lp.batch)
        out_sh = (lp.params, lp.opt, lp.replicated)
        return step_fn, in_sh, out_sh

    # -- sharded state construction / adoption ----------------------------

    def init_state(
        self,
        plan: Optional[ShardingPlan] = None,
        *,
        key: Optional[jax.Array] = None,
        init_opt: bool = True,
    ) -> Tuple[PyTree, Any]:
        """Initialize (params, opt_state) DIRECTLY as mesh shards.

        Both inits are jitted with the plan's trees as ``out_shardings``, so
        every leaf materializes on its own mesh slice — a fully replicated
        host-side param tree never exists at any point.  The only bytes that
        ever cross host->device are the PRNG seed (pass ``key`` to move even
        that out; ``benchmarks/bench_step.py`` proves the zero-transfer
        property under ``jax.transfer_guard("disallow")``).
        """
        plan = plan or self._exec_plan()
        model = self.model

        def init_fn(key):
            params, _ = model.init_params(key=key)
            return params

        if key is None:
            key = jax.random.PRNGKey(self.config.seed)
        params = jax.jit(init_fn, out_shardings=plan.params)(key)
        if not init_opt:      # caller brings its own opt_state (continuation)
            return params, None
        opt_state = jax.jit(
            self.optimizer.init, out_shardings=plan.opt
        )(params)
        return params, opt_state

    def _adopt_state(self, tree: PyTree, shardings: PyTree) -> PyTree:
        """Re-home caller-supplied state onto the live plan (a no-op when it
        already matches — e.g. continuing a run on an unchanged mesh)."""
        return jax.device_put(tree, shardings)

    # -- stage 5: training ------------------------------------------------

    def _prepare_run(
        self, params: Optional[PyTree], opt_state: Optional[PyTree]
    ) -> Tuple[CompiledStep, Any, int, PyTree, PyTree]:
        """What ``run`` does before its first step: the compiled step, the
        checkpoint manager, and the state to start from (restored, fresh,
        or the caller's re-homed onto the live plan)."""
        cfg = self.config
        compiled = self.compile()
        plan = self._exec_plan()
        ckpt = None
        if cfg.checkpoint_dir:
            if self._is_cluster():
                # coordinated save: single writer per shard, barrier at the
                # coordinator, primary publishes — same call sites below
                ckpt = ClusterCheckpointManager(
                    cfg.checkpoint_dir, keep=cfg.keep_checkpoints,
                    process_index=self._cluster.process_id,
                    num_processes=self._cluster.n_processes,
                    sync=self._cluster.sync,
                )
            else:
                ckpt = CheckpointManager(
                    cfg.checkpoint_dir, keep=cfg.keep_checkpoints
                )
        start_step = 0
        if ckpt is not None and ckpt.latest_step() is not None:
            # restart-after-failure: resume the newest valid checkpoint,
            # each leaf placed STRAIGHT onto the plan's NamedSharding — the
            # elastic path (save at dp=8, restore at dp=4) never stages a
            # fully replicated tree on any device
            params_abs, _, opt_abs = abstract_train_state(
                self.model, self.optimizer
            )
            state, meta = ckpt.restore(
                {"params": params_abs, "opt": opt_abs},
                shardings={"params": plan.params, "opt": plan.opt},
            )
            params, opt_state = state["params"], state["opt"]
            start_step = int(meta.get("step", ckpt.latest_step()))
            # resume the SAMPLING state too: without the cursors a restart
            # replays already-seen batches (and a restore-on-fewer-processes
            # run would diverge from the uninterrupted one)
            self.dataset.set_cursors(meta.get("cursors") or {})
        else:
            # no checkpoint: fresh state is BORN sharded (jitted init with
            # the plan as out_shardings); caller-supplied state (continuing
            # across an elastic event) is re-homed onto the live plan — a
            # no-op when the mesh did not change
            if params is None:
                params, fresh_opt = self.init_state(
                    plan, init_opt=opt_state is None
                )
                opt_state = opt_state if opt_state is not None else fresh_opt
            else:
                params = self._adopt_state(params, plan.params)
            if opt_state is None:
                opt_state = jax.jit(
                    self.optimizer.init, out_shardings=plan.opt
                )(params)
            else:
                opt_state = self._adopt_state(opt_state, plan.opt)
        return compiled, ckpt, start_step, params, opt_state

    def run(
        self,
        params: Optional[PyTree] = None,
        *,
        opt_state: Optional[PyTree] = None,
        steps: Optional[int] = None,
    ) -> TrainReport:
        """Train.  Pass a prior report's ``params`` AND ``opt_state`` to
        continue after an elastic event — the optimizer's moments and the
        lr-schedule step counter live in ``opt_state``, so omitting it
        restarts warmup from step 0."""
        cfg = self.config
        steps = steps or cfg.total_steps
        with Span("train.prepare"):
            compiled, ckpt, start_step, params, opt_state = (
                self._prepare_run(params, opt_state)
            )
            dataset = self.dataset
            monitor = DriftMonitor(
                margin=cfg.retune_margin, patience=cfg.retune_patience
            )
        history: List[Dict[str, float]] = []
        readbacks = 0
        t0 = time.perf_counter()

        for i in range(start_step, steps):
            # batches come THROUGH the device fleet: each dp-group's rows are
            # assembled in its storage device, and the meshfeed backend lands
            # them pre-sharded on the mesh
            with Span("train.feed") as feed:
                batch = dataset.next_device_batch()
            ts = time.perf_counter()
            with Span("train.dispatch") as dispatch:
                params, opt_state, metrics = compiled.step_fn(
                    params, opt_state, batch
                )
            with Span("train.readback") as readback:
                # an MoE step's expert rows: one read more, into the
                # process-wide counters
                rows = metrics.pop("moe_rows", None)
                metrics = {k: float(v) for k, v in metrics.items()}
                if rows is not None:
                    routed, computed, full = np.asarray(rows).tolist()
                    count("moe_rows_routed", routed)
                    count("moe_rows_computed", computed)
                    count("moe_full_buffer_layers", full)
                    count("moe_layer_steps", self.model.cfg.n_layers)
            readbacks += len(metrics) + (rows is not None)
            metrics["step_time"] = time.perf_counter() - ts
            metrics["feed_s"] = feed.seconds
            metrics["dispatch_s"] = dispatch.seconds
            metrics["readback_s"] = readback.seconds
            with Span("train.control") as control:
                history.append(metrics)
                self.callbacks.emit_step(i, metrics)

                # straggler watch: feed per-class analytic times perturbed by
                # the observed wall time (single-host stand-in for per-worker
                # probes)
                tp = self.tune()
                class_times = {
                    c.name: self.fleet.by_name(c.name).step_time(
                        tp.result.batches[c.name]
                    )
                    for c in self.fleet.classes
                    if c.name in tp.result.batches
                }
                if monitor.update(class_times):
                    self.apply(DriftDetected(source="monitor"))
                    compiled = self.compile()   # same object unless shapes grew
                    dataset = self.dataset

                if ckpt is not None and (i + 1) % cfg.checkpoint_every == 0:
                    ckpt.save(
                        i + 1, {"params": params, "opt": opt_state},
                        metadata={
                            "step": i + 1,
                            "schedule": list(
                                self.tune().schedule.group_batches
                            ),
                            "cursors": dataset.cursors(),
                        },
                        async_=cfg.async_checkpoint,
                    )
                    self.callbacks.emit_checkpoint(i + 1, cfg.checkpoint_dir)
            metrics["control_s"] = control.seconds
        if ckpt is not None:
            ckpt.wait()
        return TrainReport(
            params=params,
            opt_state=opt_state,
            history=tuple(history),
            steps_run=len(history),
            start_step=start_step,
            compile_count=self._compile_count,
            wall_time=time.perf_counter() - t0,
            readbacks=readbacks,
            attention_paths=dict(compiled.attention_paths),
        )

    # -- the ONE elastic replanning path ----------------------------------

    def apply(self, event: FleetEvent) -> ReplanResult:
        """Route any elastic fleet event through one replanning code path.

        The pinned row ``capacity`` always survives the event, so shapes only
        change when the group COUNT changes (node loss/join) — never on a
        drift re-tune.
        """
        old = self.tune()
        dropped: Tuple[str, ...] = ()

        if isinstance(event, DriftDetected):
            # membership never changes on drift: re-tune per-CLASS batches
            # and map them onto the CURRENT group workers (which may already
            # reflect earlier losses/joins)
            result = tune(self.fleet, self.benchmark)
            new_batches = tuple(
                result.batches[w.rsplit("/", 1)[0]] for w in old.group_workers
            )
            # capacity-pinned: same shapes => the compiled step survives
            schedule = old.schedule.with_batches(new_batches)
            new = TunePlan(result=result, schedule=schedule,
                           group_workers=old.group_workers)

        elif isinstance(event, WorkerLost):
            dead = set(event.workers)
            missing = dead - set(old.group_workers)
            if missing:
                raise KeyError(f"unknown workers {sorted(missing)}")
            keep = [
                (w, b) for w, b in zip(old.group_workers,
                                       old.schedule.group_batches)
                if w not in dead
            ]
            if not keep:
                raise ValueError("cannot lose every worker in the fleet")
            # shrink the fleet's class counts so later tunes/joins see the
            # true membership (a fully-dead class leaves the fleet)
            lost_per_class: Dict[str, int] = {}
            for w in dead:
                cls = w.rsplit("/", 1)[0]
                lost_per_class[cls] = lost_per_class.get(cls, 0) + 1
            self.fleet = Fleet(classes=tuple(
                dataclasses.replace(c, count=c.count - lost_per_class.get(c.name, 0))
                for c in self.fleet.classes
                if c.count - lost_per_class.get(c.name, 0) > 0
            ))
            # paper's remedy, routed through the fleet custody API: dead
            # workers' private shards are quarantined (nobody else may read
            # them — tombstoned on every surviving device), their public
            # custody re-homes to survivors; plan_epoch rebalances the share
            dropped = self.devices.quarantine_workers(sorted(dead))
            self._shards = [
                s for s in self._shards
                if not (s.private and s.owner in dead)
            ]
            # pin capacity to the pre-event max_local: fewer groups, but the
            # per-group row count is stable (no avoidable max_local shrink)
            schedule = BatchSchedule(
                tuple(b for _, b in keep),
                round_to=old.schedule.round_to,
                capacity=old.schedule.max_local,
            )
            new = TunePlan(result=old.result, schedule=schedule,
                           group_workers=tuple(w for w, _ in keep))

        elif isinstance(event, WorkerJoined):
            if any(c.name == event.class_name for c in self.fleet.classes):
                self.fleet = Fleet(classes=tuple(
                    dataclasses.replace(c, count=c.count + event.count)
                    if c.name == event.class_name else c
                    for c in self.fleet.classes
                ))
            elif event.class_name in self._class_templates:
                # the class fully died earlier; revive it from its template
                self.fleet = Fleet(classes=self.fleet.classes + (
                    dataclasses.replace(
                        self._class_templates[event.class_name],
                        count=event.count,
                    ),
                ))
            else:
                raise KeyError(event.class_name)
            result = tune(self.fleet, self.benchmark)
            # survivors keep their labels (private shards stay pinned to the
            # right physical owners); joiners draw fresh never-used indices
            # from the high-water mark, so a dead worker's label (e.g. the
            # highest index) is never recycled for a new machine
            start = self._next_index.get(event.class_name, 0)
            self._next_index[event.class_name] = start + event.count
            joiners = tuple(
                f"{event.class_name}/{start + i}" for i in range(event.count)
            )
            workers = old.group_workers + joiners
            # provision fresh storage devices for the joiners (they hold the
            # public pool; no private shards exist for a new worker yet)
            for w in joiners:
                self.devices.provision_worker(w)
            schedule = BatchSchedule(
                tuple(result.batches[w.rsplit("/", 1)[0]] for w in workers),
                round_to=old.schedule.round_to,
                capacity=old.schedule.max_local,   # never shrinks; growth
            )                                      # beyond it recompiles
            new = TunePlan(result=result, schedule=schedule,
                           group_workers=workers)

        else:
            raise TypeError(f"unknown fleet event {event!r}")

        # ---- shared tail: install the new TunePlan, re-plan, re-place ----
        compiled = self._artifacts.get("compile")
        keep_compiled = (
            compiled is not None
            and compiled.global_rows == new.schedule.global_rows
        )
        shard_plan = self._artifacts.get("shard")
        keep_shard = (
            shard_plan is not None
            and shard_plan.global_rows == new.schedule.global_rows
        )
        dataset = self._artifacts.get("dataset")
        keep_dataset = (
            dataset is not None and new.group_workers == old.group_workers
        )
        self.override("tune", new)          # invalidates plan/place/dataset
        if keep_shard:
            # same rows => same mesh: the resolved sharding plan survives
            # the event exactly like the compiled step does
            self._artifacts["shard"] = shard_plan
        if keep_compiled:
            self._artifacts["compile"] = compiled
        self.plan()
        self.place()
        if keep_dataset:
            # same membership (drift re-tune): rewire the live iterator to
            # the re-planned schedule AND placement so plan()/place() keep
            # describing what training samples, while per-worker epoch
            # cursors survive (no replay of already-seen data)
            dataset.rewire(
                new.schedule,
                manifest_sources(self.place(), list(new.group_workers)),
            )
            self._artifacts["dataset"] = dataset
        else:
            _ = self.dataset
        result_obj = ReplanResult(
            event=event, tune_plan=new,
            # only a real invalidation counts: with no step compiled yet,
            # nothing was thrown away
            recompiled=compiled is not None and not keep_compiled,
            dropped_shards=dropped,
        )
        if isinstance(event, DriftDetected):
            self.callbacks.emit_retune(event, new)
        else:
            self.callbacks.emit_fleet_change(event, result_obj)
        return result_obj
