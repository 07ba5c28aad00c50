"""The few jax runtime helpers the repo shares (targets jax 0.9.0 only).

  * :func:`make_mesh` — ``jax.make_mesh`` with every axis ``Auto``, so GSPMD
    propagates shardings the model code leaves open.
  * :func:`get_abstract_mesh` — the mesh active at trace time, or ``None``
    outside one.  ``Session`` traces its step under
    ``jax.sharding.use_abstract_mesh``; ``jax.set_mesh`` may only be
    entered outside ``jax.jit``.
  * :func:`distributed_initialize` — the ``jax.distributed.initialize``
    handshake for a multi-process job; a one-process job skips it.
  * :func:`multiprocess_compute_supported` — whether jit computations may
    SPAN processes on this backend.  CPU jaxlib can hold a global mesh,
    build per-host addressable shards, and assemble global arrays — but not
    execute a cross-process XLA program ("Multiprocess computations aren't
    implemented on the CPU backend").  The cluster runtime
    (:mod:`repro.launch.cluster`) keys its execution strategy off this:
    global-SPMD where supported, host-synchronized partial gradients
    (the paper's host-aggregation topology) where not.
"""
from __future__ import annotations

from typing import Optional, Sequence

import jax
from jax.sharding import AbstractMesh, AxisType, Mesh


def make_mesh(axis_shapes: Sequence[int], axis_names: Sequence[str]) -> Mesh:
    """``jax.make_mesh`` with Auto axis types."""
    return jax.make_mesh(
        tuple(axis_shapes), tuple(axis_names),
        axis_types=(AxisType.Auto,) * len(axis_names),
    )


def get_abstract_mesh() -> Optional[AbstractMesh]:
    """The ambient mesh visible at trace time, or ``None`` outside one."""
    m = jax.sharding.get_abstract_mesh()
    return None if m.empty else m


def distributed_initialize(
    coordinator_address: str, num_processes: int, process_id: int,
) -> bool:
    """``jax.distributed.initialize`` for a multi-process job.

    Returns True when the runtime now holds the GLOBAL device view
    (``jax.devices()`` spans all processes, ``jax.local_devices()`` is this
    host's slice), False for a one-process job, which needs no handshake.
    Idempotent: a second call on an initialized runtime is a no-op True.
    """
    if num_processes <= 1:
        return False
    # NB: do NOT probe jax.process_count() here — it initializes the
    # backend, after which jax.distributed refuses the handshake
    if jax.distributed.is_initialized():
        return True          # already initialized (e.g. by the launcher)
    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=num_processes,
        process_id=process_id,
    )
    return True


def multiprocess_compute_supported() -> bool:
    """Can a single jit computation span processes on this backend?

    CPU jaxlib supports the distributed *service* (handshake, global device
    view, cross-process array metadata) but refuses to execute multiprocess
    XLA programs.  TPU/GPU backends execute them natively.
    """
    return jax.default_backend() != "cpu"
