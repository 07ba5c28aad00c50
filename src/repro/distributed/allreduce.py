"""Explicit gradient allreduce schedules over shard_map + lax collectives.

The paper's synchronization layer is Horovod's NCCL ring allreduce.  On TPU,
XLA/GSPMD already emits near-optimal ICI collectives for a plain ``psum`` —
that is our BASELINE.  This module provides the Horovod-faithful explicit
ring (reduce-scatter ring + all-gather ring via ``lax.ppermute``) plus the
beyond-paper variants the perf loop iterates on:

  * ``ring_allreduce``          — bandwidth-optimal 2(n-1)/n ring, bit-compatible
                                  with psum (validated in tests).
  * ``hierarchical_allreduce``  — intra-pod reduce-scatter -> inter-pod
                                  allreduce on shards -> intra-pod all-gather;
                                  crosses the (slow) pod link only once with
                                  1/n_pod-sized shards.
  * ``compressed_allreduce``    — int8-quantized ring with error feedback
                                  (residual carried by the caller), 4x less
                                  ICI traffic for bandwidth-bound layers.

All functions are written per-shard (inside shard_map); `axis` names refer to
mesh axes.  They operate on a single flat vector — the caller flattens the
grad pytree (bucketing is in :func:`bucketize`).
"""
from __future__ import annotations

import functools
from typing import Callable, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from jax.sharding import Mesh, PartitionSpec as P

from repro.kernels import ops as kops

PyTree = jax.Array  # flat vectors in this module


# ---------------------------------------------------------------------------
# Ring allreduce (Horovod-faithful)
# ---------------------------------------------------------------------------


def ring_allreduce(x: jax.Array, axis: str) -> jax.Array:
    """Reduce-scatter ring + all-gather ring along ``axis``.

    Inside shard_map: every device holds an identical-shape ``x``; the result
    is the elementwise sum across the axis (== lax.psum(x, axis)), moved in
    2(n-1) ring hops of 1/n-size chunks — each device sends/receives
    2(n-1)/n of the payload, the bandwidth-optimal schedule the paper's
    Horovod uses.
    """
    n = jax.lax.axis_size(axis)
    if n == 1:
        return x
    idx = jax.lax.axis_index(axis)
    size = x.shape[0]
    pad = (-size) % n
    if pad:
        x = jnp.pad(x, (0, pad))
    chunks = x.reshape(n, -1)                       # chunk c lives at row c
    fwd = [(i, (i + 1) % n) for i in range(n)]

    # reduce-scatter: after n-1 hops, device d owns the full sum of chunk
    # (d+1) mod n.  Each hop sends the chunk we just accumulated.
    def rs_body(k, chunks):
        # at hop k, device d sends chunk (d - k) mod n, receives (d - k - 1)
        send_ix = (idx - k) % n
        recv_ix = (idx - k - 1) % n
        sent = jax.lax.ppermute(chunks[send_ix], axis, fwd)
        return chunks.at[recv_ix].add(sent)

    chunks = jax.lax.fori_loop(0, n - 1, rs_body, chunks)

    # all-gather ring: device d owns the reduced chunk (d+1) mod n; circulate
    def ag_body(k, chunks):
        send_ix = (idx + 1 - k) % n
        recv_ix = (idx - k) % n
        sent = jax.lax.ppermute(chunks[send_ix], axis, fwd)
        return chunks.at[recv_ix].set(sent)

    chunks = jax.lax.fori_loop(0, n - 1, ag_body, chunks)
    out = chunks.reshape(-1)
    return out[:size] if pad else out


# ---------------------------------------------------------------------------
# Hierarchical (multi-pod) allreduce
# ---------------------------------------------------------------------------


def hierarchical_allreduce(
    x: jax.Array, *, intra_axis: str, inter_axis: str
) -> jax.Array:
    """reduce_scatter(intra) -> psum(inter) on 1/n shards -> all_gather(intra).

    The inter-pod link (DCN / optical, ~10x slower than ICI) carries only
    ``bytes / n_intra`` per device instead of full ``bytes`` — the standard
    fleet-scale schedule, here explicit so the roofline's collective term can
    attribute bytes to the right fabric.
    """
    n_intra = jax.lax.axis_size(intra_axis)
    size = x.shape[0]
    pad = (-size) % n_intra
    if pad:
        x = jnp.pad(x, (0, pad))
    shard = jax.lax.psum_scatter(
        x.reshape(n_intra, -1), intra_axis, scatter_dimension=0, tiled=False
    )                                               # (chunk,) partial sums
    shard = jax.lax.psum(shard, inter_axis)         # cross-pod on 1/n bytes
    out = jax.lax.all_gather(shard, intra_axis, axis=0, tiled=False).reshape(-1)
    return out[:size] if pad else out


# ---------------------------------------------------------------------------
# Compressed ring (int8 + error feedback)
# ---------------------------------------------------------------------------


def compressed_allreduce(
    x: jax.Array,
    residual: jax.Array,
    noise: jax.Array,
    *,
    axis: str,
    rows: int = 256,
) -> Tuple[jax.Array, jax.Array]:
    """Quantized allreduce with error feedback.

    q = int8(x + residual); allreduce the int8 payload (here: psum over the
    dequantized values — on hardware the int8 tensor rides the wire and is
    summed in int32); new_residual = (x + residual) - dequant(q).
    Returns (summed dequantized gradient, new residual).
    """
    y = x + residual
    size = y.shape[0]
    pad = (-size) % rows
    if pad:
        y2 = jnp.pad(y, (0, pad))
        noise = jnp.pad(noise, (0, pad))
    else:
        y2 = y
    mat = y2.reshape(rows, -1)
    q, scale = kops.quantize_int8(
        mat, noise.reshape(rows, -1), interpret=kops.interpret_default()
    )
    deq = kops.dequantize_int8(q, scale).reshape(-1)[:size]
    new_residual = y - deq
    total = jax.lax.psum(deq, axis)
    return total, new_residual


# ---------------------------------------------------------------------------
# Bucketing (Horovod-style fusion buffers)
# ---------------------------------------------------------------------------


def flatten_grads(grads) -> Tuple[jax.Array, Callable]:
    """Concatenate a grad pytree into one f32 vector + unflattener."""
    leaves, treedef = jax.tree_util.tree_flatten(grads)
    shapes = [l.shape for l in leaves]
    sizes = [int(l.size) for l in leaves]
    dtypes = [l.dtype for l in leaves]
    flat = jnp.concatenate([l.astype(jnp.float32).reshape(-1) for l in leaves])

    def unflatten(vec: jax.Array):
        out, off = [], 0
        for shape, size, dt in zip(shapes, sizes, dtypes):
            out.append(vec[off : off + size].reshape(shape).astype(dt))
            off += size
        return jax.tree_util.tree_unflatten(treedef, out)

    return flat, unflatten


def bucketize(flat: jax.Array, bucket_bytes: int = 64 * 1024 * 1024) -> List[jax.Array]:
    """Split a flat f32 vector into Horovod-style fusion buckets."""
    per = max(1, bucket_bytes // 4)
    return [flat[i : i + per] for i in range(0, flat.shape[0], per)]


# ---------------------------------------------------------------------------
# shard_map drivers (what the trainer/pjit integrates)
# ---------------------------------------------------------------------------


def make_ring_psum(mesh: Mesh, axis: str = "data") -> Callable:
    """Returns f(grads_pytree) -> summed pytree using the explicit ring.

    Applied inside shard_map over ``axis``; every other mesh axis must be
    replicated for the grads (DP gradients are replicated over model).
    """
    from jax.experimental.shard_map import shard_map

    def allreduce(grads):
        flat, unflatten = flatten_grads(grads)

        ring = shard_map(
            lambda v: ring_allreduce(v, axis),
            mesh=mesh,
            in_specs=P(),     # replicated input (per-device local grads differ
            out_specs=P(),    #  only mathematically — shapes are identical)
            check_rep=False,
        )
        return unflatten(ring(flat))

    return allreduce
