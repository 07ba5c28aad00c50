"""Frozen stage artifacts produced by the Session pipeline.

Each pipeline stage returns one immutable artifact:

    Session.tune()    -> TunePlan            (Algorithm 1 + group schedule)
    Session.plan()    -> core EpochPlan      (Eq. 1 dataset shares)
    Session.place()   -> core PlacementManifest  (privacy placement)
    Session.shard()   -> ShardingPlan        (rule table resolved on the mesh)
    Session.compile() -> CompiledStep        (the jitted SPMD step)
    Session.run()     -> TrainReport

``EpochPlan`` and ``PlacementManifest`` already live in :mod:`repro.core`
(they are the paper's own objects); this module adds the session-level ones.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Tuple

from repro.core.hetero import BatchSchedule
from repro.core.tuner import TuneResult

PyTree = Any


@dataclasses.dataclass(frozen=True)
class TunePlan:
    """Algorithm-1 output expanded to physical dp-groups.

    ``schedule.capacity`` pins the row capacity: re-tunes that fit under it
    keep the compiled step's shapes (and therefore never recompile).
    """

    result: TuneResult
    schedule: BatchSchedule
    group_workers: Tuple[str, ...]

    @property
    def batches(self) -> Dict[str, int]:
        return self.result.batches


# The ShardingPlan artifact class lives in :mod:`repro.distributed.sharding`
# (beside the rule engine that resolves it) so layers below the api package
# — train/steps, storage/meshfeed, checkpoint — can type against it without
# importing the whole Session surface; it is re-exported here because it IS
# a Session stage artifact (``Session.shard()``'s return value).
from repro.distributed.sharding import ShardingPlan  # noqa: E402,F401


@dataclasses.dataclass(frozen=True)
class CompiledStep:
    """The jitted train step plus the shape signature it was built for.

    ``build_id`` is the session-wide compile counter — the probe tests use
    to assert that a drift re-tune did NOT trigger a rebuild.
    ``in_shardings``/``out_shardings`` record the explicit ShardingPlan trees
    the step was jitted with (``None`` only for externally built steps).
    ``attention_paths`` counts, per path, the attention layers of the step's
    latest trace (``{"splash": 2}``: two layers on the TPU kernel pair); it
    is filled when the step is first called.
    """

    step_fn: Callable
    global_rows: int
    seq_len: int
    valid_rows: int           # lr-schedule anchor at build time
    build_id: int
    config_key: Tuple = ()    # the SessionConfig values baked into the step
    in_shardings: Any = None  # (params, opt, batch) NamedSharding trees
    out_shardings: Any = None
    attention_paths: Dict[str, int] = dataclasses.field(default_factory=dict)

    def signature(self) -> Tuple[int, int]:
        return (self.global_rows, self.seq_len)


@dataclasses.dataclass(frozen=True)
class TrainReport:
    """What a training run produced (``Session.run``'s return value).

    ``opt_state`` lets a caller continue training seamlessly after an
    elastic event: ``session.run(report.params, opt_state=report.opt_state)``
    keeps optimizer moments and the lr-schedule step counter.

    Each ``history`` entry holds the step's metrics, ``step_time`` (dispatch
    to metrics on the host), and the host seconds of the loop's spans:
    ``feed_s``, ``dispatch_s``, ``readback_s`` and ``control_s``.
    ``readbacks`` counts the step metrics the call read back to the host, each
    a blocking device-to-host read.  ``attention_paths`` is the compiled
    step's tally of attention layers by path (:class:`CompiledStep`).
    """

    params: PyTree
    opt_state: Any
    history: Tuple[Dict[str, float], ...]
    steps_run: int
    start_step: int
    compile_count: int
    wall_time: float
    readbacks: int
    attention_paths: Dict[str, int] = dataclasses.field(default_factory=dict)

    @property
    def final_loss(self) -> float:
        return self.history[-1]["loss"] if self.history else float("nan")


@dataclasses.dataclass(frozen=True)
class ReplanResult:
    """Outcome of ``Session.apply(event)`` — one per elastic event."""

    event: Any
    tune_plan: TunePlan
    recompiled: bool          # False => shapes survived, no XLA rebuild
    dropped_shards: Tuple[str, ...] = ()
