"""`MeshFeedDevice`: per-dp-group feeding onto a real `jax.sharding.Mesh`.

The first two backends hand the Session one host-side global batch that jit
then scatters — fine on one device, but it re-stages the whole batch through
device 0 on a real mesh.  This backend models what a rack of CSDs actually
does: each device assembles ITS dp-group's rows locally, and the host never
holds more than views; the global array is stitched together from
per-device shards via :func:`jax.make_array_from_single_device_arrays`
(the multi-host feeding idiom), already laid out along the mesh's ``data``
axis.

Per-host feeding is the PRIMARY path: :meth:`MeshFeeder.feed_addressable`
takes only the rows THIS host owns (plus their offset into the global
batch), slices them by the sharding's own index map restricted to the
**addressable** devices, and ``device_put``s exactly those pieces — nothing
else.  The global array is then assembled from the single-device shards
under ``jax.transfer_guard_host_to_device("disallow")``, which turns the
"no cross-host batch bytes" invariant into a runtime guarantee: any byte
that would need to move beyond the addressable puts is a hard error, and
the per-feed :class:`FeedReceipt` records exactly which devices received
how many bytes.  The single-process :meth:`MeshFeeder.feed` is now just
``feed_addressable`` over the full row window (offset 0).

Device ↔ mesh mapping: the global Stannis batch is ``(n_groups *
max_local, seq)`` group-major.  The feed splits those rows into
``data_axis_size`` contiguous chunks — one per mesh device along ``data`` —
so dp-group g's rows land on the mesh slice that computes group g.  In a
multi-process cluster the mesh is the :func:`~repro.launch.mesh.
make_cluster_mesh` contract (process-major device order), so a process's
addressable chunks are exactly its dp-groups' rows.

Sampling custody is inherited from :class:`SyntheticDevice` — mesh feeding
changes where batches *land*, never who may *read* a shard.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Tuple

import numpy as np

from repro.storage.synthetic import SyntheticDevice


class MeshFeedDevice(SyntheticDevice):
    """Synthetic sampling + mesh-placed batch delivery (see module doc)."""

    backend = "meshfeed"


def data_axis_size(global_rows: int, n_devices: int) -> int:
    """Largest divisor of ``global_rows`` that fits the device count."""
    if global_rows <= 0:
        return 1
    for d in range(min(n_devices, global_rows), 0, -1):
        if global_rows % d == 0:
            return d
    return 1


@dataclasses.dataclass(frozen=True)
class FeedReceipt:
    """Byte-exact accounting of ONE per-host feed (the invariant's proof).

    ``bytes_put`` is every host->device byte this feed moved; every
    destination in ``devices`` is addressable by construction (the index
    map is restricted to addressable devices), and the global-array
    assembly that followed ran under a host->device transfer guard — so
    ``bytes_put`` is the TOTAL h2d traffic of the feed, and none of it
    crossed a process boundary.
    """

    rows_local: int                  # host rows this process supplied
    rows_global: int                 # rows of the assembled global batch
    bytes_put: int                   # h2d bytes actually moved (all keys)
    n_puts: int                      # device_put calls issued
    devices: Tuple[int, ...]         # destination device ids (addressable)
    process_index: int               # which process fed

    @property
    def local_fraction(self) -> float:
        return self.rows_local / max(1, self.rows_global)


class MeshFeeder:
    """Builds (and re-builds, when the row count changes across elastic
    events) the feed mesh, and lands host batches onto it per-shard.

    When a session's :class:`~repro.api.artifacts.ShardingPlan` is adopted
    (:meth:`adopt_shardings`), batches land with the PLAN's ``NamedSharding``
    per key — the layout the compiled step declares as ``in_shardings`` —
    instead of a locally re-derived one, so the feed and the step can never
    disagree about placement.  Stale plans (from before an elastic mesh
    resize) are detected by mesh mismatch and ignored until the session
    adopts the re-derived plan.

    In a cluster, ``adopt_shardings`` may also carry per-key LOCAL
    shardings (the hostsync compute layout over this process's mesh):
    :meth:`feed_addressable` then assembles the local view from the SAME
    single-device buffers whenever the two index maps agree — the local
    compute arrays literally are the global arrays' addressable shards,
    zero extra transfers.
    """

    def __init__(self, data_axis: Optional[int] = None):
        self._forced = data_axis
        self._mesh = None
        self._rows = None
        self._shardings: Dict[str, object] = {}
        self._local_shardings: Dict[str, object] = {}
        self._plan_rows: Optional[int] = None
        self.last_receipt: Optional[FeedReceipt] = None
        self.last_local: Optional[Dict[str, object]] = None

    def mesh_for(self, global_rows: int):
        import jax

        from repro.launch.mesh import make_host_mesh

        if self._mesh is None or self._rows != global_rows:
            d = self._forced or data_axis_size(global_rows, len(jax.devices()))
            if global_rows % d != 0:
                raise ValueError(
                    f"data axis {d} does not divide global_rows {global_rows}"
                )
            self._mesh = make_host_mesh(data=d, model=1)
            self._rows = global_rows
        return self._mesh

    @property
    def n_feed_devices(self) -> int:
        return 0 if self._mesh is None else int(self._mesh.shape["data"])

    def adopt_shardings(
        self,
        shardings: Dict[str, object],
        local: Optional[Dict[str, object]] = None,
        *,
        global_rows: Optional[int] = None,
    ) -> None:
        """Adopt a ShardingPlan's per-key batch ``NamedSharding``s (and, in a
        cluster, the local compute shardings the hostsync step consumes).

        ``global_rows`` records the row count the plan was resolved for:
        a feed of a DIFFERENT row count (mid-replan, before the session
        re-adopts) ignores the stale plan and falls back to a locally
        derived mesh, exactly like the pre-cluster behavior.
        """
        self._shardings = dict(shardings)
        self._local_shardings = dict(local) if local else {}
        self._plan_rows = global_rows
        if global_rows is not None and self._shardings:
            # the plan's mesh IS the feed mesh for that row count (in a
            # cluster it spans processes — never derivable from mesh_for)
            self._mesh = next(iter(self._shardings.values())).mesh
            self._rows = int(global_rows)

    def _sharding_for(self, key: str, v_shape, mesh):
        from jax.sharding import NamedSharding, PartitionSpec as P

        sharding = self._shardings.get(key)
        if sharding is None or sharding.mesh != mesh:
            # no (or stale) plan: default row sharding over ``data``
            sharding = NamedSharding(
                mesh, P("data", *([None] * (len(v_shape) - 1)))
            )
        return sharding

    def feed(self, batch: Dict[str, np.ndarray]) -> Dict:
        """Single-host delivery: the full row window, offset 0."""
        return self.feed_addressable(batch)

    def feed_addressable(
        self,
        batch: Dict[str, np.ndarray],
        *,
        row_offset: int = 0,
        global_rows: Optional[int] = None,
    ) -> Dict:
        """Place THIS host's rows onto its addressable mesh slice, per-shard.

        ``batch`` holds only the local rows; ``row_offset``/``global_rows``
        situate them in the global batch (defaults: the batch IS the global
        batch).  Every ``device_put`` destination comes from the sharding's
        own ``addressable_devices_indices_map`` — a non-addressable device
        can never appear — and the global arrays are assembled from the
        single-device shards under a host->device transfer guard, so the
        :class:`FeedReceipt` in ``last_receipt`` accounts for every h2d
        byte the feed moved.  Raises if the addressable slice reaches
        beyond the rows this host holds (custody/mesh misalignment).
        """
        import jax

        local_rows = next(iter(batch.values())).shape[0]
        R = global_rows if global_rows is not None else local_rows
        adopted_ok = bool(self._shardings) and self._plan_rows == R
        mesh = (
            next(iter(self._shardings.values())).mesh
            if adopted_ok else self.mesh_for(R)
        )
        out: Dict[str, jax.Array] = {}
        local_out: Dict[str, jax.Array] = {}
        bytes_put = 0
        n_puts = 0
        dev_ids = set()
        want_local = bool(self._local_shardings)
        for k, v in batch.items():
            gshape = (R,) + v.shape[1:]
            sharding = self._sharding_for(k, gshape, mesh)
            idx_map = sharding.addressable_devices_indices_map(gshape)
            pieces = {}
            for dev, idx in sorted(idx_map.items(), key=lambda kv: kv[0].id):
                rs = idx[0] if idx else slice(None)
                start = rs.start or 0
                stop = rs.stop if rs.stop is not None else R
                if start < row_offset or stop > row_offset + local_rows:
                    raise ValueError(
                        f"addressable slice [{start}:{stop}) of {k!r} falls "
                        f"outside this host's rows "
                        f"[{row_offset}:{row_offset + local_rows}) — feed "
                        f"mesh and shard custody disagree"
                    )
                piece = v[start - row_offset:stop - row_offset, ...]
                pieces[dev] = jax.device_put(piece, dev)
                bytes_put += piece.nbytes
                n_puts += 1
                dev_ids.add(dev.id)
            # assembly is zero-copy: prove it by disallowing further h2d
            with jax.transfer_guard_host_to_device("disallow"):
                out[k] = jax.make_array_from_single_device_arrays(
                    gshape, sharding, list(pieces.values())
                )
                if want_local:
                    local_out[k] = self._assemble_local(
                        k, v.shape, pieces, row_offset
                    )
        self.last_receipt = FeedReceipt(
            rows_local=int(local_rows),
            rows_global=int(R),
            bytes_put=int(bytes_put),
            n_puts=int(n_puts),
            devices=tuple(sorted(dev_ids)),
            process_index=jax.process_index(),
        )
        self.last_local = local_out if want_local else None
        return out

    def _assemble_local(self, key, local_shape, pieces, row_offset):
        """The LOCAL (hostsync compute) view over the same device buffers.

        Valid only when the local sharding's index map tiles the local rows
        with exactly the pieces the global feed already placed (same
        devices, same row chunks) — guaranteed by construction when the
        local mesh's ``data`` axis is the per-process share of the global
        one and both meshes enumerate this process's devices in id order.
        A mismatch raises (custody/mesh misalignment), it never silently
        moves extra bytes.
        """
        import jax

        lsh = self._local_shardings.get(key)
        if lsh is None:
            return None
        lshape = tuple(local_shape)
        lmap = lsh.addressable_devices_indices_map(lshape)
        shards = []
        for dev, idx in sorted(lmap.items(), key=lambda kv: kv[0].id):
            rs = idx[0] if idx else slice(None)
            start = (rs.start or 0) + row_offset
            stop = (rs.stop if rs.stop is not None else lshape[0]) + row_offset
            piece = pieces.get(dev)
            if piece is None or piece.shape[0] != stop - start:
                raise ValueError(
                    f"local sharding of {key!r} wants rows [{start}:{stop}) "
                    f"on {dev} but the global feed placed "
                    f"{None if piece is None else piece.shape} there"
                )
            shards.append(piece)
        return jax.make_array_from_single_device_arrays(lshape, lsh, shards)
