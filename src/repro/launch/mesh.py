"""Production mesh construction.

Functions (never module-level constants) so importing this module never
touches jax device state — the dry-run sets XLA_FLAGS before first jax use.

Production target: TPU v5e pods, 16x16 = 256 chips per pod.
  single pod: ("data", "model") = (16, 16)
  multi-pod:  ("pod", "data", "model") = (2, 16, 16) = 512 chips
Stannis dp-groups live along ("pod", "data"); tensor/expert parallel along
"model".

``make_host_mesh`` is the CPU-device mesh the storage layer's
:class:`~repro.storage.meshfeed.MeshFeedDevice` backend feeds per-dp-group
batches onto (smoke tests force N host devices via
``XLA_FLAGS=--xla_force_host_platform_device_count=N``).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional, Tuple

import jax
from jax.sharding import Mesh

from repro.compat import make_mesh, multiprocess_compute_supported


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(
    *, data: int = 1, model: int = 1, axis_names: Tuple[str, ...] = ("data", "model")
) -> Mesh:
    """Small mesh over however many (CPU) devices exist — smoke tests."""
    n = len(jax.devices())
    if data < 1 or model < 1:
        raise ValueError(
            f"mesh axes must be positive, got data={data}, model={model}"
        )
    if data * model > n:
        raise ValueError(
            f"host mesh ({data} x {model}) needs {data * model} devices "
            f"but only {n} are available; set "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={data * model} "
            f"or shrink the mesh"
        )
    return make_mesh((data, model), axis_names)


def make_single_mesh(
    axis_names: Tuple[str, ...] = ("data", "model")
) -> Mesh:
    """Degenerate 1x1 mesh over one device.

    Host-delivery storage backends (synthetic / flash) have no feed mesh of
    their own; ``Session.shard()`` resolves the rule table against this mesh
    so the SAME sharding-explicit compile path (explicit ``in_shardings``,
    jitted sharded init) runs on a laptop CPU and a pod alike.
    """
    return make_mesh((1,) * len(axis_names), axis_names)


# ---------------------------------------------------------------------------
# Cluster (multi-process) meshes
# ---------------------------------------------------------------------------


def cluster_data_axis(
    global_rows: int, n_devices: int, n_processes: int
) -> int:
    """Largest ``data`` axis that divides ``global_rows``, fits ``n_devices``,
    and is a multiple of ``n_processes`` — so the row chunks never straddle a
    process boundary (each process's rows land only on its own devices).
    Falls back to ``n_processes`` itself (one chunk per process)."""
    if global_rows <= 0:
        return n_processes
    for d in range(min(n_devices, global_rows), n_processes - 1, -1):
        if d % n_processes == 0 and global_rows % d == 0:
            return d
    return n_processes


def pick_cluster_devices(devices, data: int, model: int, n_processes: int):
    """An EQUAL share of ``data * model`` devices from every process.

    Taking the first ``data * model`` of the process-major order would be
    wrong whenever the data axis is smaller than the global device count:
    early processes would contribute extra devices and their addressable
    chunks would spill past their custody row slab.  Each process must
    contribute exactly ``data * model / n_processes`` devices (in id
    order) so chunk ownership and row custody coincide.
    """
    need = data * model
    if need % n_processes:
        raise ValueError(
            f"cluster mesh ({data} x {model}) does not split over "
            f"{n_processes} processes"
        )
    share = need // n_processes
    by_proc: dict = {}
    for d in sorted(devices, key=lambda d: (d.process_index, d.id)):
        by_proc.setdefault(d.process_index, []).append(d)
    if len(by_proc) != n_processes:
        raise ValueError(
            f"global device view spans {len(by_proc)} processes, "
            f"expected {n_processes}"
        )
    picked = []
    for p in sorted(by_proc):
        if len(by_proc[p]) < share:
            raise ValueError(
                f"process {p} has {len(by_proc[p])} devices but the mesh "
                f"needs {share} from each process"
            )
        picked.extend(by_proc[p][:share])
    return picked


def make_cluster_mesh(
    *,
    data: int,
    model: int = 1,
    n_processes: int = 1,
    axis_names: Tuple[str, ...] = ("data", "model"),
) -> Mesh:
    """The GLOBAL mesh of a multi-process cluster.

    Spans every process's devices (``jax.devices()``), process-major with
    an EQUAL device share per process (see :func:`pick_cluster_devices`),
    so the ``data`` axis's contiguous row chunks align with process
    ownership: process ``p``'s addressable devices cover exactly the row
    slab ``[p*R/P, (p+1)*R/P)``.  Built the same way in EVERY process —
    the mesh is the shared contract, each process only ever ``device_put``s
    to its addressable slice of it.
    """
    import numpy as np

    devs = pick_cluster_devices(jax.devices(), data, model, n_processes)
    grid = np.array(devs).reshape(data, model)
    return Mesh(grid, axis_names)


@dataclasses.dataclass
class ClusterContext:
    """This process's identity inside a multi-process cluster.

    Built by :class:`repro.launch.cluster.WorkerRuntime` after the
    ``jax.distributed`` handshake and attached to a ``Session``
    (:meth:`~repro.api.session.Session.attach_cluster`).  ``mode`` selects
    the execution strategy:

      * ``"spmd"``     — jit computations may span processes (TPU/GPU):
        the global-mesh step consumes globally-sharded arrays directly.
      * ``"hostsync"`` — the backend cannot execute cross-process programs
        (CPU jaxlib): each process computes partial gradients on a LOCAL
        mesh over its addressable devices and sums them through the
        coordinator (the paper's host-aggregation topology).  Numerically
        identical to the global step for dense models (the masked loss is a
        ratio of across-process sums).

    ``sync`` is the coordinator control-plane client (duck-typed:
    ``allreduce(tag, tree) -> tree`` and ``barrier(tag)``); ``None`` for a
    single-process compat fallback.  ``transport`` is the gradient wire
    (:func:`repro.launch.transport.build_wire_transport` — star or ring)
    configured by ``transport_spec``; the session's hostsync compile wraps
    it in a :class:`~repro.launch.transport.GradReducer` cached here as
    ``grad_reducer`` so error-feedback residuals survive recompiles.
    """

    process_id: int
    n_processes: int
    mode: str = "hostsync"                 # "spmd" | "hostsync"
    sync: Any = None
    member: Optional[str] = None           # membership id (heartbeat name)
    transport: Any = None                  # wire layer (star/ring), or None
    transport_spec: Any = None             # TransportSpec, or None
    grad_reducer: Any = None               # GradReducer cache (set at compile)

    def __post_init__(self):
        if self.mode not in ("spmd", "hostsync"):
            raise ValueError(f"unknown cluster mode {self.mode!r}")

    @classmethod
    def detect(cls, process_id: int, n_processes: int, sync=None,
               member: Optional[str] = None, transport=None,
               transport_spec=None) -> "ClusterContext":
        mode = "spmd" if multiprocess_compute_supported() else "hostsync"
        return cls(process_id=process_id, n_processes=n_processes,
                   mode=mode, sync=sync, member=member,
                   transport=transport, transport_spec=transport_spec)

    @property
    def is_primary(self) -> bool:
        return self.process_id == 0

    def global_mesh(self, global_rows: int) -> Mesh:
        d = cluster_data_axis(
            global_rows, len(jax.devices()), self.n_processes
        )
        return make_cluster_mesh(
            data=d, model=1, n_processes=self.n_processes
        )

    def local_mesh(self, local_rows: int, data_axis: Optional[int] = None) -> Mesh:
        """Mesh over THIS process's devices (the hostsync compute mesh).

        ``data_axis`` pins the chunk count — pass the per-process share of
        the global mesh's ``data`` axis so the local index map tiles rows
        with EXACTLY the pieces the global feed placed (the zero-extra-copy
        local view in :meth:`MeshFeeder.feed_addressable`)."""
        import numpy as np

        devs = sorted(jax.local_devices(), key=lambda d: d.id)
        d = data_axis
        if d is None:
            d = 1
            for cand in range(min(len(devs), max(1, local_rows)), 0, -1):
                if local_rows % cand == 0:
                    d = cand
                    break
        if d > len(devs) or (local_rows and local_rows % d):
            raise ValueError(
                f"local mesh data axis {d} invalid for {local_rows} rows "
                f"on {len(devs)} local devices"
            )
        grid = np.array(devs[:d]).reshape(d, 1)
        return Mesh(grid, ("data", "model"))
