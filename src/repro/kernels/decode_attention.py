"""Single-token decode attention (the serving hot spot) as a Pallas kernel.

Flash-decoding adapted to TPU: one query row per (batch, head) attends to the
KV cache in VMEM-sized chunks; running (m, l, acc) stats carried in scratch
across the innermost grid dimension (TPU sequential grid), masked by each
batch row's valid cache length.  The (B,) valid lengths are scalar-prefetched
into SMEM: a (1, 1) VMEM block of a (B, 1) array is not a legal TPU tiling.

q block is a single row (1, D); to keep the MXU fed the kv chunk (bk, D) is
multiplied as (bk, D) x (D, 1) — a skinny matmul the TPU lowers to VPU+MXU
hybrid; bk = 512 amortizes control overhead across the cache sweep.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _online_update(q, k, v, first_k, valid, window, m_ref, l_ref, acc_ref):
    """One online-softmax step: fold the (bk, D) chunk at offset ``first_k``
    into the running (m, l, acc) scratch stats.  q is pre-scaled (1, D) f32;
    k/v are already-dequantized (bk, D) f32."""
    bk = k.shape[0]
    s = jax.lax.dot_general(                                # (1, bk)
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )
    k_pos = first_k + jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1)
    mask = k_pos < valid
    if window is not None:
        mask &= k_pos > (valid - 1 - window)
    s = jnp.where(mask, s, NEG_INF)
    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=1, keepdims=True)
    acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    m_ref[...] = m_new


def _decode_kernel(
    valid_ref, q_ref, k_ref, v_ref, o_ref,
    m_ref, l_ref, acc_ref,
    *,
    scale: float,
    window: Optional[int],
    bk: int,
    n_kv: int,
):
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    valid = valid_ref[pl.program_id(0)]                     # () int32
    first_k = ik * bk
    live = first_k < valid

    @pl.when(live)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32) * scale         # (1, D)
        k = k_ref[0, 0].astype(jnp.float32)                 # (bk, D)
        v = v_ref[0, 0].astype(jnp.float32)                 # (bk, D)
        _online_update(q, k, v, first_k, valid, window, m_ref, l_ref, acc_ref)

    @pl.when(ik == n_kv - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


def _decode_int8_kernel(
    valid_ref, q_ref, k_ref, ks_ref, v_ref, vs_ref, o_ref,
    m_ref, l_ref, acc_ref,
    *,
    scale: float,
    window: Optional[int],
    bk: int,
    n_kv: int,
):
    """:func:`_decode_kernel` over an int8 cache: the (bk, D) int8 chunk and
    its (bk, 1) per-row scales are dequantized in VMEM — HBM only ever moves
    the int8 bytes (+1/4·D scale column), ~4x less than the f32 cache."""
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    valid = valid_ref[pl.program_id(0)]
    first_k = ik * bk
    live = first_k < valid

    @pl.when(live)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32) * scale         # (1, D)
        k = k_ref[0, 0].astype(jnp.float32) * ks_ref[0, 0]  # (bk, D) * (bk, 1)
        v = v_ref[0, 0].astype(jnp.float32) * vs_ref[0, 0]
        _online_update(q, k, v, first_k, valid, window, m_ref, l_ref, acc_ref)

    @pl.when(ik == n_kv - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


def _paged_decode_kernel(
    table_ref,                  # scalar-prefetch: (B, NP) int32 block table
    valid_ref,                  # scalar-prefetch: (B,) int32 valid lengths
    q_ref, k_ref, v_ref, o_ref,
    m_ref, l_ref, acc_ref,
    *,
    scale: float,
    window: Optional[int],
    page_size: int,
    n_pages: int,
):
    """Online-softmax decode over pool-resident KV pages.

    Identical math to :func:`_decode_kernel`, but the KV chunk for grid step
    (b, h, j) is DMA'd straight from page ``table[b, j]`` of the shared pool —
    the block table is scalar-prefetched so the index map can address pages
    before the body runs.  Shared prefix pages are fetched per-sequence but
    stored once (ref-counted by the serve-side BlockAllocator).  The pool
    arrives head-flattened, (P, page, Hkv * D), so one head's rows of a page
    are a (page, D) block."""
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    valid = valid_ref[pl.program_id(0)]
    first_k = j * page_size
    live = first_k < valid

    @pl.when(live)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32) * scale         # (1, D)
        k = k_ref[0].astype(jnp.float32)                    # (page, D)
        v = v_ref[0].astype(jnp.float32)
        _online_update(q, k, v, first_k, valid, window, m_ref, l_ref, acc_ref)

    @pl.when(j == n_pages - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


def _paged_decode_int8_kernel(
    table_ref,                  # scalar-prefetch: (B, NP) int32 block table
    valid_ref,                  # scalar-prefetch: (B,) int32 valid lengths
    q_ref, k_ref, ks_ref, v_ref, vs_ref, o_ref,
    m_ref, l_ref, acc_ref,
    *,
    scale: float,
    window: Optional[int],
    page_size: int,
    n_pages: int,
    group: int,
):
    """:func:`_paged_decode_kernel` over int8 pages + per-row scale pages;
    dequantize happens in VMEM after the page DMA.  A scale page holds every
    kv head's column, (page, Hkv); the kernel picks its own with a mask."""
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    valid = valid_ref[pl.program_id(0)]
    first_k = j * page_size
    live = first_k < valid
    col = jax.lax.broadcasted_iota(jnp.int32, ks_ref.shape[1:], 1)
    own = col == pl.program_id(1) // group                  # (page, Hkv)

    @pl.when(live)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32) * scale         # (1, D)
        ks = jnp.sum(jnp.where(own, ks_ref[0], 0.0), axis=1, keepdims=True)
        vs = jnp.sum(jnp.where(own, vs_ref[0], 0.0), axis=1, keepdims=True)
        k = k_ref[0].astype(jnp.float32) * ks               # (page, D)
        v = v_ref[0].astype(jnp.float32) * vs
        _online_update(q, k, v, first_k, valid, window, m_ref, l_ref, acc_ref)

    @pl.when(j == n_pages - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


def paged_decode_attention(
    q: jax.Array,               # (B, 1, H, D)
    k_pages: jax.Array,         # (P, page_size, Hkv, D)  shared page pool
    v_pages: jax.Array,
    block_table: jax.Array,     # (B, NP) int32 page ids per sequence
    valid_len: jax.Array,       # (B,) int32 valid positions per sequence
    *,
    window: Optional[int] = None,
    interpret: bool = False,
) -> jax.Array:
    """Decode attention driven by a block table over a paged KV pool.

    The block-table counterpart of :func:`decode_attention`: instead of a
    per-sequence contiguous cache, KV lives once in a ref-counted page pool
    and each sequence brings a table of page ids — the serving engine's
    paged-gather hot path (prefix blocks shared between sequences are read
    in place, never materialized per sequence)."""
    B, _, H, D = q.shape
    n_pool, page_size, Hkv = k_pages.shape[0], k_pages.shape[1], k_pages.shape[2]
    NP = block_table.shape[1]
    assert H % Hkv == 0
    group = H // Hkv

    qt = jnp.moveaxis(q, 2, 1)                              # (B, H, 1, D)
    table = block_table.astype(jnp.int32)
    flat = lambda t: t.reshape(n_pool, page_size, Hkv * t.shape[3])

    kernel = functools.partial(
        _paged_decode_kernel,
        scale=1.0 / math.sqrt(D), window=window,
        page_size=page_size, n_pages=NP,
    )
    page_spec = pl.BlockSpec(
        (1, page_size, D),
        lambda b, h, j, tbl, n, g=group: (tbl[b, j], 0, h // g),
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, H, NP),
        in_specs=[
            pl.BlockSpec((1, 1, 1, D), lambda b, h, j, tbl, n: (b, h, 0, 0)),
            page_spec,
            page_spec,
        ],
        out_specs=pl.BlockSpec(
            (1, 1, 1, D), lambda b, h, j, tbl, n: (b, h, 0, 0)
        ),
        scratch_shapes=[
            pltpu.VMEM((1, 1), jnp.float32),
            pltpu.VMEM((1, 1), jnp.float32),
            pltpu.VMEM((1, D), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(qt.shape, q.dtype),
        interpret=interpret,
    )(table, valid_len.astype(jnp.int32), qt, flat(k_pages), flat(v_pages))
    return jnp.moveaxis(out, 1, 2)                          # (B, 1, H, D)


def paged_decode_attention_int8(
    q: jax.Array,               # (B, 1, H, D)
    k_pages: jax.Array,         # (P, page_size, Hkv, D) int8 page pool
    k_scales: jax.Array,        # (P, page_size, Hkv, 1) f32 per-row scales
    v_pages: jax.Array,
    v_scales: jax.Array,
    block_table: jax.Array,     # (B, NP) int32
    valid_len: jax.Array,       # (B,) int32
    *,
    window: Optional[int] = None,
    interpret: bool = False,
) -> jax.Array:
    """:func:`paged_decode_attention` over an int8 page pool.

    The pool stores int8 KV rows + f32 per-row scales; each page is DMA'd as
    int8 (plus its scale column) and dequantized inside the kernel — the
    decode sweep moves ~1/4 the KV bytes of the f32 pool."""
    B, _, H, D = q.shape
    n_pool, page_size, Hkv = k_pages.shape[0], k_pages.shape[1], k_pages.shape[2]
    NP = block_table.shape[1]
    assert H % Hkv == 0
    group = H // Hkv

    qt = jnp.moveaxis(q, 2, 1)                              # (B, H, 1, D)
    table = block_table.astype(jnp.int32)
    flat = lambda t: t.reshape(n_pool, page_size, Hkv * t.shape[3])

    kernel = functools.partial(
        _paged_decode_int8_kernel,
        scale=1.0 / math.sqrt(D), window=window,
        page_size=page_size, n_pages=NP, group=group,
    )
    page_spec = pl.BlockSpec(
        (1, page_size, D),
        lambda b, h, j, tbl, n, g=group: (tbl[b, j], 0, h // g),
    )
    scale_spec = pl.BlockSpec(
        (1, page_size, Hkv), lambda b, h, j, tbl, n: (tbl[b, j], 0, 0)
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, H, NP),
        in_specs=[
            pl.BlockSpec((1, 1, 1, D), lambda b, h, j, tbl, n: (b, h, 0, 0)),
            page_spec, scale_spec, page_spec, scale_spec,
        ],
        out_specs=pl.BlockSpec(
            (1, 1, 1, D), lambda b, h, j, tbl, n: (b, h, 0, 0)
        ),
        scratch_shapes=[
            pltpu.VMEM((1, 1), jnp.float32),
            pltpu.VMEM((1, 1), jnp.float32),
            pltpu.VMEM((1, D), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(qt.shape, q.dtype),
        interpret=interpret,
    )(table, valid_len.astype(jnp.int32), qt, flat(k_pages), flat(k_scales),
      flat(v_pages), flat(v_scales))
    return jnp.moveaxis(out, 1, 2)                          # (B, 1, H, D)


def decode_attention(
    q: jax.Array,               # (B, 1, H, D)
    k: jax.Array,               # (B, Skv, Hkv, D)  cache
    v: jax.Array,
    valid_len: jax.Array,       # (B,) int32
    *,
    window: Optional[int] = None,
    block_k: int = 512,
    interpret: bool = False,
) -> jax.Array:
    B, _, H, D = q.shape
    Skv, Hkv = k.shape[1], k.shape[2]
    assert H % Hkv == 0
    group = H // Hkv
    bk = min(block_k, max(Skv, 8))

    qt = jnp.moveaxis(q, 2, 1)                    # (B, H, 1, D)
    kt = jnp.moveaxis(k, 2, 1)                    # (B, Hkv, Skv, D)
    vt = jnp.moveaxis(v, 2, 1)
    pad_k = (-Skv) % bk
    if pad_k:
        kt = jnp.pad(kt, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
        vt = jnp.pad(vt, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
    n_kv = kt.shape[2] // bk

    kernel = functools.partial(
        _decode_kernel, scale=1.0 / math.sqrt(D), window=window, bk=bk, n_kv=n_kv
    )
    kv_spec = pl.BlockSpec(
        (1, 1, bk, D), lambda b, h, ik, n, g=group: (b, h // g, ik, 0)
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, H, n_kv),
            in_specs=[
                pl.BlockSpec((1, 1, 1, D), lambda b, h, ik, n: (b, h, 0, 0)),
                kv_spec, kv_spec,
            ],
            out_specs=pl.BlockSpec(
                (1, 1, 1, D), lambda b, h, ik, n: (b, h, 0, 0)
            ),
            scratch_shapes=[
                pltpu.VMEM((1, 1), jnp.float32),
                pltpu.VMEM((1, 1), jnp.float32),
                pltpu.VMEM((1, D), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct(qt.shape, q.dtype),
        interpret=interpret,
    )(valid_len.astype(jnp.int32), qt, kt, vt)
    return jnp.moveaxis(out, 1, 2)                # (B, 1, H, D)


def decode_attention_int8(
    q: jax.Array,               # (B, 1, H, D)
    k: jax.Array,               # (B, Skv, Hkv, D) int8 cache
    k_scale: jax.Array,         # (B, Skv, Hkv, 1) f32 per-row scales
    v: jax.Array,
    v_scale: jax.Array,
    valid_len: jax.Array,       # (B,) int32
    *,
    window: Optional[int] = None,
    block_k: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """:func:`decode_attention` over an int8 cache + per-row scales,
    dequantized chunk-by-chunk inside the kernel."""
    B, _, H, D = q.shape
    Skv, Hkv = k.shape[1], k.shape[2]
    assert H % Hkv == 0
    assert k_scale.shape == (B, Skv, Hkv, 1), k_scale.shape
    group = H // Hkv
    bk = min(block_k, max(Skv, 8))

    qt = jnp.moveaxis(q, 2, 1)                    # (B, H, 1, D)
    kt = jnp.moveaxis(k, 2, 1)                    # (B, Hkv, Skv, D)
    vt = jnp.moveaxis(v, 2, 1)
    kst = jnp.moveaxis(k_scale, 2, 1)             # (B, Hkv, Skv, 1)
    vst = jnp.moveaxis(v_scale, 2, 1)
    pad_k = (-Skv) % bk
    if pad_k:
        pad = ((0, 0), (0, 0), (0, pad_k), (0, 0))
        kt, vt, kst, vst = (jnp.pad(t, pad) for t in (kt, vt, kst, vst))
    n_kv = kt.shape[2] // bk

    kernel = functools.partial(
        _decode_int8_kernel,
        scale=1.0 / math.sqrt(D), window=window, bk=bk, n_kv=n_kv,
    )
    kv_spec = lambda shape: pl.BlockSpec(
        shape, lambda b, h, ik, n, g=group: (b, h // g, ik, 0)
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, H, n_kv),
            in_specs=[
                pl.BlockSpec((1, 1, 1, D), lambda b, h, ik, n: (b, h, 0, 0)),
                kv_spec((1, 1, bk, D)),
                kv_spec((1, 1, bk, 1)),
                kv_spec((1, 1, bk, D)),
                kv_spec((1, 1, bk, 1)),
            ],
            out_specs=pl.BlockSpec(
                (1, 1, 1, D), lambda b, h, ik, n: (b, h, 0, 0)
            ),
            scratch_shapes=[
                pltpu.VMEM((1, 1), jnp.float32),
                pltpu.VMEM((1, 1), jnp.float32),
                pltpu.VMEM((1, D), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct(qt.shape, q.dtype),
        interpret=interpret,
    )(valid_len.astype(jnp.int32), qt, kt, kst, vt, vst)
    return jnp.moveaxis(out, 1, 2)                # (B, 1, H, D)
