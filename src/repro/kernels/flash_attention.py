"""Flash attention (forward) as a Pallas TPU kernel.

TPU-native design (not a CUDA port):
  * Grid ``(B, H, Sq/bq, Skv/bk)`` — the KV dimension iterates INNERMOST, so
    the online-softmax running stats (m, l, acc) live in VMEM scratch and are
    carried across grid steps on the same core (TPU grids execute
    sequentially per core; no atomics / shared-memory reductions needed).
  * Block shapes: q (bq, D), k/v (bk, D) with bq/bk multiples of the 128-lane
    MXU tile; the two matmuls per block (q @ k^T and p @ v) hit the MXU at
    full tile occupancy for D in {64, 128, 256}.
  * GQA without materialization: the kv BlockSpec index_map divides the head
    index (h -> h // group) so K/V blocks are fetched once per kv-head group
    straight from HBM — the repeat happens in the dataflow, never in memory.
  * Causal/local-window masking is done by block skip (pl.when over the whole
    block) + within-block iota masks, so fully-masked blocks cost no FLOPs.

The backward (``repro.kernels.ops._flash_bwd``) differentiates the oracle
``flash_attention_ref``, which materialises the full S x S f32 scores: a
correctness oracle, not a training path at long sequences.  Training
attention on a TPU (causal, no window) takes the splash forward and dq/dkv
backward kernels bundled with JAX instead
(``repro.kernels.ops.splash_causal_attention``, routed by
``repro.models.layers.splash_route``).
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


def _attn_body(
    q_ref, kv_load, o_ref,               # q block, kv loader, out block
    m_ref, l_ref, acc_ref,               # VMEM scratch carried over kv steps
    *,
    scale: float,
    causal: bool,
    window: Optional[int],
    bq: int,
    bk: int,
    n_kv: int,
    seq_q: int,
    seq_kv: int,
):
    """Shared online-softmax sweep; ``kv_load() -> (k, v)`` f32 (bk, D) tiles.

    The int8 variant dequantizes inside ``kv_load`` — the running stats,
    masking, and MXU matmuls are identical, so both precisions share one
    sweep implementation."""
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_pos = iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    k_pos = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)

    def _compute():
        q = q_ref[0, 0].astype(jnp.float32) * scale    # (bq, D)
        k, v = kv_load()                               # (bk, D) each, f32
        s = jax.lax.dot_general(                       # (bq, bk) on the MXU
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        mask = k_pos < seq_kv                          # right padding
        if causal:
            mask &= k_pos <= q_pos
        if window is not None:
            mask &= k_pos > (q_pos - window)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]                            # (bq, 1)
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)
        p = jnp.where(mask, p, 0.0)
        corr = jnp.exp(m_prev - m_new)                 # (bq, 1)
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        m_ref[...] = m_new

    if causal or window is not None:
        # whole-block skip: block is live iff any (q, k) pair is unmasked
        first_q, last_q = iq * bq, iq * bq + bq - 1
        first_k, last_k = ik * bk, ik * bk + bk - 1
        live = jnp.bool_(True)
        if causal:
            live &= first_k <= last_q
        if window is not None:
            live &= last_k > first_q - window
        pl.when(live)(_compute)
    else:
        _compute()

    @pl.when(ik == n_kv - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


def _attn_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, **kw):
    _attn_body(
        q_ref,
        lambda: (k_ref[0, 0].astype(jnp.float32), v_ref[0, 0].astype(jnp.float32)),
        o_ref, m_ref, l_ref, acc_ref, **kw,
    )


def _attn_int8_kernel(
    q_ref, k_ref, ks_ref, v_ref, vs_ref, o_ref, m_ref, l_ref, acc_ref, **kw
):
    # int8 K/V tiles ride with (bk, 1) f32 per-row scales on the same index
    # map; dequantize as the tile enters the sweep — K/V never exist in f32
    # outside this VMEM-resident block.
    _attn_body(
        q_ref,
        lambda: (
            k_ref[0, 0].astype(jnp.float32) * ks_ref[0, 0],
            v_ref[0, 0].astype(jnp.float32) * vs_ref[0, 0],
        ),
        o_ref, m_ref, l_ref, acc_ref, **kw,
    )


def flash_attention_fwd(
    q: jax.Array,                # (B, Sq, H, D)
    k: jax.Array,                # (B, Skv, Hkv, D)
    v: jax.Array,
    *,
    causal: bool = False,
    window: Optional[int] = None,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = False,
) -> jax.Array:
    B, Sq, H, D = q.shape
    Skv, Hkv = k.shape[1], k.shape[2]
    assert H % Hkv == 0, (H, Hkv)
    group = H // Hkv
    bq = min(block_q, max(Sq, 8))
    bk = min(block_k, max(Skv, 8))

    # (B, S, H, D) -> (B, H, S, D): contiguous (S, D) blocks per (batch, head)
    qt = jnp.moveaxis(q, 2, 1)
    kt = jnp.moveaxis(k, 2, 1)
    vt = jnp.moveaxis(v, 2, 1)

    pad_q = (-Sq) % bq
    pad_k = (-Skv) % bk
    if pad_q:
        qt = jnp.pad(qt, ((0, 0), (0, 0), (0, pad_q), (0, 0)))
    if pad_k:
        kt = jnp.pad(kt, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
        vt = jnp.pad(vt, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
    n_q = qt.shape[2] // bq
    n_kv = kt.shape[2] // bk

    grid = (B, H, n_q, n_kv)
    kernel = functools.partial(
        _attn_kernel,
        scale=1.0 / math.sqrt(D),
        causal=causal,
        window=window,
        bq=bq,
        bk=bk,
        n_kv=n_kv,
        seq_q=Sq,
        seq_kv=Skv,
    )
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, D), lambda b, h, iq, ik: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, bk, D), lambda b, h, iq, ik, g=group: (b, h // g, ik, 0)),
            pl.BlockSpec((1, 1, bk, D), lambda b, h, iq, ik, g=group: (b, h // g, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, D), lambda b, h, iq, ik: (b, h, iq, 0)),
        out_shape=jax.ShapeDtypeStruct(qt.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),     # m
            pltpu.VMEM((bq, 1), jnp.float32),     # l
            pltpu.VMEM((bq, D), jnp.float32),     # acc
        ],
        interpret=interpret,
    )(qt, kt, vt)
    if pad_q:
        out = out[:, :, :Sq]
    return jnp.moveaxis(out, 1, 2)


def flash_attention_int8_fwd(
    q: jax.Array,                # (B, Sq, H, D) float
    k: jax.Array,                # (B, Skv, Hkv, D) int8
    k_scale: jax.Array,          # (B, Skv, Hkv, 1) f32 per-row scales
    v: jax.Array,                # (B, Skv, Hkv, D) int8
    v_scale: jax.Array,          # (B, Skv, Hkv, 1) f32
    *,
    causal: bool = False,
    window: Optional[int] = None,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """Flash attention over int8 K/V with in-sweep dequantization.

    Same grid/blocking as :func:`flash_attention_fwd`; the scale operands
    ride (1, 1, bk, 1) BlockSpecs on the K/V index map (GQA head-group
    divide included), so a K/V tile and its row scales always arrive
    together and the f32 K/V tile exists only inside VMEM.
    """
    B, Sq, H, D = q.shape
    Skv, Hkv = k.shape[1], k.shape[2]
    assert H % Hkv == 0, (H, Hkv)
    assert k.dtype == jnp.int8 and v.dtype == jnp.int8, (k.dtype, v.dtype)
    group = H // Hkv
    bq = min(block_q, max(Sq, 8))
    bk = min(block_k, max(Skv, 8))

    qt = jnp.moveaxis(q, 2, 1)
    kt = jnp.moveaxis(k, 2, 1)
    vt = jnp.moveaxis(v, 2, 1)
    kst = jnp.moveaxis(k_scale, 2, 1)
    vst = jnp.moveaxis(v_scale, 2, 1)

    pad_q = (-Sq) % bq
    pad_k = (-Skv) % bk
    if pad_q:
        qt = jnp.pad(qt, ((0, 0), (0, 0), (0, pad_q), (0, 0)))
    if pad_k:
        kv_pad = ((0, 0), (0, 0), (0, pad_k), (0, 0))
        kt = jnp.pad(kt, kv_pad)
        vt = jnp.pad(vt, kv_pad)
        kst = jnp.pad(kst, kv_pad)   # zero scales: pad rows dequantize to 0
        vst = jnp.pad(vst, kv_pad)
    n_q = qt.shape[2] // bq
    n_kv = kt.shape[2] // bk

    grid = (B, H, n_q, n_kv)
    kernel = functools.partial(
        _attn_int8_kernel,
        scale=1.0 / math.sqrt(D),
        causal=causal,
        window=window,
        bq=bq,
        bk=bk,
        n_kv=n_kv,
        seq_q=Sq,
        seq_kv=Skv,
    )
    kv_spec = pl.BlockSpec(
        (1, 1, bk, D), lambda b, h, iq, ik, g=group: (b, h // g, ik, 0)
    )
    sc_spec = pl.BlockSpec(
        (1, 1, bk, 1), lambda b, h, iq, ik, g=group: (b, h // g, ik, 0)
    )
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, D), lambda b, h, iq, ik: (b, h, iq, 0)),
            kv_spec, sc_spec, kv_spec, sc_spec,
        ],
        out_specs=pl.BlockSpec((1, 1, bq, D), lambda b, h, iq, ik: (b, h, iq, 0)),
        out_shape=jax.ShapeDtypeStruct(qt.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),     # m
            pltpu.VMEM((bq, 1), jnp.float32),     # l
            pltpu.VMEM((bq, D), jnp.float32),     # acc
        ],
        interpret=interpret,
    )(qt, kt, kst, vt, vst)
    if pad_q:
        out = out[:, :, :Sq]
    return jnp.moveaxis(out, 1, 2)
