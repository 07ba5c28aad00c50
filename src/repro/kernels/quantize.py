"""Int8 gradient quantization with stochastic rounding — the compressed-
allreduce building block (beyond-paper distributed optimization).

Per-row symmetric quantization: scale = absmax / 127.  Stochastic rounding
(floor(x/scale + uniform)) keeps E[q*scale] = x, so momentum-SGD stays
unbiased; the residual (error feedback) is handled by the caller in
:mod:`repro.distributed.allreduce`.

Kernel layout: rows tiled to (block_rows, N) VMEM blocks; absmax reduce and
the scale/round/clip are all VPU element ops — this kernel is purely
bandwidth-bound, which is the point: it converts an ICI-bandwidth-bound
allreduce into a (4x smaller) one at the cost of HBM traffic that overlaps.
The uniform noise is passed in as an operand (generated with the training
PRNG) so the kernel stays deterministic per seed on every backend.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import interpret_default


# bytes of one f32 (rows, N) input block: x and noise, each double-buffered,
# plus the int8 output stay inside v5e's 16 MiB default scoped VMEM
_BLOCK_BYTES = 2 << 20


def _quant_kernel(x_ref, noise_ref, q_ref, scale_ref):
    x = x_ref[...].astype(jnp.float32)                    # (br, N)
    absmax = jnp.max(jnp.abs(x), axis=1, keepdims=True)
    scale = jnp.maximum(absmax / 127.0, 1e-12)
    y = x / scale
    q = jnp.floor(y + noise_ref[...].astype(jnp.float32))
    q = jnp.clip(q, -127, 127)
    q_ref[...] = q.astype(jnp.int8)
    scale_ref[...] = scale


def _quantize_rows(
    x: jax.Array,               # (R, N) float
    noise: jax.Array,           # (R, N) rounding offsets in [0, 1)
    *,
    block_rows: int = 256,
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """The one row-quantization core: pad, tile, kernel, un-pad.

    Every int8 producer in the repo funnels through here — the gradient-
    transport flat path, the KV-cache path, and the quantized-training
    residual path — so the rounding semantics (``floor(x/scale + noise)``,
    i.e. round-half-up at ``noise=0.5``) are pinned in exactly one place.
    """
    R, N = x.shape
    assert noise.shape == x.shape, (noise.shape, x.shape)
    # pad-and-mask for any R: the row block is sublane-aligned (multiple of
    # 8, so ragged R also compiles on TPU), rows pad with zeros — per-row
    # scales mean padding never contaminates real rows — and the pad rows
    # are sliced back off below.  Wide rows shrink the block to the VMEM
    # budget.
    fit = max(8, _BLOCK_BYTES // (4 * N) // 8 * 8)
    br = min(block_rows, fit, ((R + 7) // 8) * 8)
    pad = (-R) % br
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0)))
        noise = jnp.pad(noise, ((0, pad), (0, 0)))
    Rp = x.shape[0]
    q, scale = pl.pallas_call(
        _quant_kernel,
        grid=(Rp // br,),
        in_specs=[
            pl.BlockSpec((br, N), lambda i: (i, 0)),
            pl.BlockSpec((br, N), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((br, N), lambda i: (i, 0)),
            pl.BlockSpec((br, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Rp, N), jnp.int8),
            jax.ShapeDtypeStruct((Rp, 1), jnp.float32),
        ],
        interpret=interpret,
    )(x, noise)
    return q[:R], scale[:R]


def quantize_int8(
    x: jax.Array,               # (R, N) float
    noise: jax.Array,           # (R, N) uniform [0,1)
    *,
    block_rows: int = 256,
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    return _quantize_rows(x, noise, block_rows=block_rows, interpret=interpret)


def dequantize_int8(q: jax.Array, scale: jax.Array, dtype=jnp.float32) -> jax.Array:
    return (q.astype(jnp.float32) * scale).astype(dtype)


# ---------------------------------------------------------------------------
# Flat-vector form — the cluster gradient transport's unit of work
# ---------------------------------------------------------------------------
#
# The host transport ships grads as flat f32 vectors (one per layer bucket).
# ``quantize_flat`` reshapes a vector into (ceil(n/chunk), chunk) rows so the
# shared ``_quantize_rows`` core gives one scale per ``chunk`` contiguous
# elements.  Rounding is the deterministic round-half-up (constant noise
# 0.5): every worker quantizes its OWN contribution once and every peer
# decodes the same int8 bytes, so determinism across replicas costs nothing;
# the quantization bias is absorbed by the caller's error-feedback residual.


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def _quantize_flat_jit(vec: jax.Array, chunk: int, interpret: bool):
    n = vec.shape[0]
    rows = -(-n // chunk)
    pad = rows * chunk - n
    mat = jnp.pad(vec.astype(jnp.float32), (0, pad)).reshape(rows, chunk)
    noise = jnp.full((rows, chunk), 0.5, jnp.float32)
    return _quantize_rows(mat, noise, interpret=interpret)


def quantize_flat(
    vec: jax.Array, *, chunk: int = 512
) -> Tuple[jax.Array, jax.Array]:
    """Quantize a flat f32 vector to (q int8 (rows, chunk), scale f32 (rows, 1))."""
    return _quantize_flat_jit(jnp.asarray(vec), chunk, interpret_default())


def dequantize_flat(q, scale, size: int):
    """Numpy-side inverse of :func:`quantize_flat` (peers decode on host)."""
    import numpy as np

    q = np.asarray(q)
    scale = np.asarray(scale, dtype=np.float32)
    return (q.astype(np.float32) * scale).reshape(-1)[:size]
