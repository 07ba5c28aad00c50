"""Pure-jnp oracles for every Pallas kernel (the allclose targets).

Each function is the mathematical definition, written for clarity not speed;
tests sweep shapes/dtypes and assert the kernels match these.
"""
from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp


# ---------------------------------------------------------------------------
# Attention
# ---------------------------------------------------------------------------


def flash_attention_ref(
    q: jax.Array,               # (B, Sq, H, D)
    k: jax.Array,               # (B, Skv, Hkv, D)
    v: jax.Array,               # (B, Skv, Hkv, D)
    *,
    causal: bool = False,
    window: Optional[int] = None,
    q_offset: int = 0,
) -> jax.Array:
    B, Sq, H, D = q.shape
    Skv, Hkv = k.shape[1], k.shape[2]
    if Hkv != H:
        reps = H // Hkv
        k = jnp.repeat(k, reps, axis=2)
        v = jnp.repeat(v, reps, axis=2)
    scale = 1.0 / math.sqrt(D)
    logits = jnp.einsum(
        "bqhd,bkhd->bhqk", q.astype(jnp.float32), k.astype(jnp.float32)
    ) * scale
    q_pos = jnp.arange(Sq) + q_offset
    k_pos = jnp.arange(Skv)
    mask = jnp.ones((Sq, Skv), bool)
    if causal:
        mask &= k_pos[None, :] <= q_pos[:, None]
    if window is not None:
        mask &= k_pos[None, :] > (q_pos[:, None] - window)
    logits = jnp.where(mask[None, None], logits, -jnp.inf)
    probs = jax.nn.softmax(logits, axis=-1)
    probs = jnp.where(jnp.isnan(probs), 0.0, probs)  # fully-masked rows
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v.astype(jnp.float32)).astype(q.dtype)


def splash_causal_attention_ref(
    q: jax.Array, k: jax.Array, v: jax.Array,
) -> jax.Array:
    """Oracle of the splash training pair: exact causal attention, f32."""
    return flash_attention_ref(q, k, v, causal=True)


def decode_attention_ref(
    q: jax.Array,               # (B, 1, H, D)
    k: jax.Array,               # (B, Skv, Hkv, D)  (cache)
    v: jax.Array,               # (B, Skv, Hkv, D)
    valid_len: jax.Array,       # (B,) int32 — positions < valid_len attend
    *,
    window: Optional[int] = None,
) -> jax.Array:
    B, _, H, D = q.shape
    Skv, Hkv = k.shape[1], k.shape[2]
    if Hkv != H:
        reps = H // Hkv
        k = jnp.repeat(k, reps, axis=2)
        v = jnp.repeat(v, reps, axis=2)
    scale = 1.0 / math.sqrt(D)
    logits = jnp.einsum(
        "bqhd,bkhd->bhqk", q.astype(jnp.float32), k.astype(jnp.float32)
    ) * scale
    k_pos = jnp.arange(Skv)[None, :]
    mask = k_pos < valid_len[:, None]
    if window is not None:
        mask &= k_pos > (valid_len[:, None] - 1 - window)
    logits = jnp.where(mask[:, None, None, :], logits, -jnp.inf)
    probs = jax.nn.softmax(logits, axis=-1)
    probs = jnp.where(jnp.isnan(probs), 0.0, probs)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v.astype(jnp.float32)).astype(q.dtype)


def paged_decode_attention_ref(
    q: jax.Array,               # (B, 1, H, D)
    k_pages: jax.Array,         # (P, page_size, Hkv, D)
    v_pages: jax.Array,
    block_table: jax.Array,     # (B, NP) int32
    valid_len: jax.Array,       # (B,) int32
    *,
    window: Optional[int] = None,
) -> jax.Array:
    """Gather each row's pages into a contiguous cache, then dense decode."""
    B, NP = block_table.shape
    page_size, Hkv, D = k_pages.shape[1:]
    k = k_pages[block_table].reshape(B, NP * page_size, Hkv, D)
    v = v_pages[block_table].reshape(B, NP * page_size, Hkv, D)
    return decode_attention_ref(q, k, v, valid_len, window=window)


def decode_attention_int8_ref(
    q: jax.Array,               # (B, 1, H, D)
    k: jax.Array,               # (B, Skv, Hkv, D) int8 cache
    k_scale: jax.Array,         # (B, Skv, Hkv, 1) f32 per-row scales
    v: jax.Array,               # (B, Skv, Hkv, D) int8
    v_scale: jax.Array,         # (B, Skv, Hkv, 1) f32
    valid_len: jax.Array,       # (B,) int32
    *,
    window: Optional[int] = None,
) -> jax.Array:
    """Dequantize the int8 cache, then dense decode (the fused kernel's target)."""
    kf = dequantize_int8_ref(k, k_scale, jnp.float32)
    vf = dequantize_int8_ref(v, v_scale, jnp.float32)
    return decode_attention_ref(q, kf, vf, valid_len, window=window)


def paged_decode_attention_int8_ref(
    q: jax.Array,               # (B, 1, H, D)
    k_pages: jax.Array,         # (P, page_size, Hkv, D) int8
    k_scales: jax.Array,        # (P, page_size, Hkv, 1) f32
    v_pages: jax.Array,
    v_scales: jax.Array,
    block_table: jax.Array,     # (B, NP) int32
    valid_len: jax.Array,       # (B,) int32
    *,
    window: Optional[int] = None,
) -> jax.Array:
    """Gather int8 pages + scales, dequantize, then dense decode."""
    B, NP = block_table.shape
    page_size, Hkv, D = k_pages.shape[1:]
    k = dequantize_int8_ref(
        k_pages[block_table], k_scales[block_table], jnp.float32
    ).reshape(B, NP * page_size, Hkv, D)
    v = dequantize_int8_ref(
        v_pages[block_table], v_scales[block_table], jnp.float32
    ).reshape(B, NP * page_size, Hkv, D)
    return decode_attention_ref(q, k, v, valid_len, window=window)


# ---------------------------------------------------------------------------
# Mixture of experts
# ---------------------------------------------------------------------------


def grouped_matmul_ref(
    lhs: jax.Array, rhs: jax.Array, group_sizes: jax.Array,
) -> jax.Array:
    """Row r of ``lhs`` times ``rhs[g]`` for the group g whose consecutive
    rows hold r (``group_sizes`` rows each, from row 0); rows past the last
    group are zero.  f32 products, output in lhs's dtype."""
    ends = jnp.cumsum(group_sizes)
    row = jnp.arange(lhs.shape[0])
    group = jnp.searchsorted(ends, row, side="right")       # (M,)
    out = jnp.zeros((lhs.shape[0], rhs.shape[-1]), jnp.float32)
    for g in range(rhs.shape[0]):
        y = lhs.astype(jnp.float32) @ rhs[g].astype(jnp.float32)
        out = jnp.where((group == g)[:, None], y, out)
    return out.astype(lhs.dtype)


def fused_moe_mlp_ref(
    x: jax.Array,               # (T, d) tokens
    router: jax.Array,          # (d, E)
    wg: jax.Array,              # (E, d, f) gate proj
    wu: jax.Array,              # (E, d, f) up proj
    wo: jax.Array,              # (E, f, d) down proj
    *,
    k: int,
    capacity: int,
) -> Tuple[jax.Array, jax.Array]:
    """Capacity-layout top-k MoE with SwiGLU experts (Switch aux loss).

    The mathematical definition of the fused dispatch+GEMM kernel: top-k
    routing with renormalized gates, first-come-first-served capacity at
    ``capacity`` slots per expert (overflow copies dropped), per-expert
    SwiGLU, gate-weighted combine.  Returns ``(out (T, d), aux_loss)``.
    """
    T, d = x.shape
    E = router.shape[1]
    C = capacity

    logits = (x @ router.astype(jnp.float32)).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_ids = jax.lax.top_k(probs, k)
    gate_vals = gate_vals / jnp.sum(gate_vals, axis=-1, keepdims=True)

    tok_frac = jnp.mean(
        jax.nn.one_hot(expert_ids, E, dtype=jnp.float32).sum(axis=1), axis=0
    )
    prob_frac = jnp.mean(probs, axis=0)
    aux = E * jnp.sum(tok_frac * prob_frac)

    flat_expert = expert_ids.reshape(-1)
    flat_token = jnp.repeat(jnp.arange(T), k)
    flat_gate = gate_vals.reshape(-1)
    order = jnp.argsort(flat_expert, stable=True)
    se, st, sg = flat_expert[order], flat_token[order], flat_gate[order]

    counts = jnp.bincount(flat_expert, length=E)
    offsets = jnp.concatenate([jnp.zeros(1, counts.dtype), jnp.cumsum(counts)[:-1]])
    pos_in_expert = jnp.arange(T * k) - offsets[se]
    keep = pos_in_expert < C
    slot = jnp.where(keep, se * C + pos_in_expert, E * C)

    gathered = x[st] * keep[:, None].astype(x.dtype)
    buf = jnp.zeros((E * C + 1, d), x.dtype).at[slot].set(gathered)[: E * C]
    buf = buf.reshape(E, C, d)

    g = jnp.einsum("ecd,edf->ecf", buf, wg)
    u = jnp.einsum("ecd,edf->ecf", buf, wu)
    h = jax.nn.silu(g) * u
    y = jnp.einsum("ecf,efd->ecd", h, wo).reshape(E * C, d)

    safe_slot = jnp.minimum(slot, E * C - 1)
    gate_w = (sg * keep).astype(y.dtype)
    out = jnp.zeros((T, d), y.dtype).at[st].add(y[safe_slot] * gate_w[:, None])
    return out, aux.astype(jnp.float32)


# ---------------------------------------------------------------------------
# Linear recurrences
# ---------------------------------------------------------------------------


def rglru_scan_ref(
    a: jax.Array,               # (B, S, W) decay in (0, 1)
    x: jax.Array,               # (B, S, W) gated input
    h0: Optional[jax.Array] = None,  # (B, W)
) -> jax.Array:
    """h_t = a_t * h_{t-1} + x_t; returns all h_t. float32 internally."""
    af, xf = a.astype(jnp.float32), x.astype(jnp.float32)

    def step(h, inp):
        at, xt = inp
        h = at * h + xt
        return h, h

    h_init = jnp.zeros((a.shape[0], a.shape[2]), jnp.float32) if h0 is None \
        else h0.astype(jnp.float32)
    _, ys = jax.lax.scan(
        step, h_init, (jnp.moveaxis(af, 1, 0), jnp.moveaxis(xf, 1, 0))
    )
    return jnp.moveaxis(ys, 0, 1).astype(x.dtype)


def rwkv6_scan_ref(
    r: jax.Array, k: jax.Array, v: jax.Array, w: jax.Array,  # (B, S, H, D)
    u: jax.Array,                                            # (H, D)
    s0: Optional[jax.Array] = None,                          # (B, H, D, D)
) -> Tuple[jax.Array, jax.Array]:
    """out_t = r_t @ (S_{t-1} + u*k_t (x) v_t);  S_t = diag(w_t) S_{t-1} + k_t (x) v_t."""
    B, S, H, D = r.shape
    s = jnp.zeros((B, H, D, D), jnp.float32) if s0 is None else s0.astype(jnp.float32)

    def step(s, xs):
        rt, kt, vt, wt = xs
        kv = kt[..., :, None] * vt[..., None, :]
        out = jnp.einsum("bhd,bhde->bhe", rt, s + u[..., :, None] * kv)
        s = wt[..., :, None] * s + kv
        return s, out

    xs = tuple(jnp.moveaxis(t.astype(jnp.float32), 1, 0) for t in (r, k, v, w))
    s, outs = jax.lax.scan(step, s, xs)
    return jnp.moveaxis(outs, 0, 1).astype(r.dtype), s


# ---------------------------------------------------------------------------
# Gradient quantization (compressed allreduce)
# ---------------------------------------------------------------------------


def quantize_int8_ref(
    x: jax.Array,               # (..., N) float
    noise: Optional[jax.Array] = None,  # same shape, U[0,1) for stochastic rounding
) -> Tuple[jax.Array, jax.Array]:
    """Per-row symmetric int8: scale = absmax/127; stochastic or nearest round.

    Returns (q int8, scale f32 with trailing dim 1).
    """
    xf = x.astype(jnp.float32)
    absmax = jnp.max(jnp.abs(xf), axis=-1, keepdims=True)
    scale = jnp.maximum(absmax / 127.0, 1e-12)
    y = xf / scale
    if noise is None:
        q = jnp.round(y)
    else:
        q = jnp.floor(y + noise.astype(jnp.float32))
    q = jnp.clip(q, -127, 127).astype(jnp.int8)
    return q, scale


def dequantize_int8_ref(q: jax.Array, scale: jax.Array, dtype=jnp.float32) -> jax.Array:
    return (q.astype(jnp.float32) * scale).astype(dtype)


# ---------------------------------------------------------------------------
# Quantized-training (q8) ops: quantize → dequantize → base oracle
# ---------------------------------------------------------------------------
#
# Each q8 op quantizes its streamed activations with the deterministic
# round-half-up the Pallas quantize kernel uses (constant 0.5 noise — pinned
# by the quantize parity tests), then runs the base math on the dequantized
# values.  The fused kernels dequantize in-VMEM instead, so op and oracle see
# the SAME int8 values and differ only by the usual kernel-vs-ref float
# reassociation.


def _q8_rows(x: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Per-row round-half-up int8 (the q8 training quantizer)."""
    return quantize_int8_ref(x, jnp.full(x.shape, 0.5, jnp.float32))


def _q8_roundtrip(x: jax.Array) -> jax.Array:
    q, s = _q8_rows(x)
    return dequantize_int8_ref(q, s, jnp.float32)


def flash_attention_q8_ref(
    q: jax.Array,               # (B, Sq, H, D)
    k: jax.Array,               # (B, Skv, Hkv, D)
    v: jax.Array,
    *,
    causal: bool = False,
    window: Optional[int] = None,
) -> jax.Array:
    """Flash attention with K/V squeezed through per-row int8."""
    return flash_attention_ref(
        q, _q8_roundtrip(k), _q8_roundtrip(v), causal=causal, window=window
    )


def rwkv6_scan_q8_ref(
    r: jax.Array, k: jax.Array, v: jax.Array, w: jax.Array,  # (B, S, H, D)
    u: jax.Array,                                            # (H, D)
) -> Tuple[jax.Array, jax.Array]:
    """WKV scan with r/k/v squeezed through per-row int8 (decay stays f32)."""
    out, s = rwkv6_scan_ref(
        _q8_roundtrip(r), _q8_roundtrip(k), _q8_roundtrip(v),
        w.astype(jnp.float32), u,
    )
    return out.astype(r.dtype), s


def rglru_scan_q8_ref(
    a: jax.Array,               # (B, S, W) decay in (0, 1)
    x: jax.Array,               # (B, S, W) gated input
) -> jax.Array:
    """RG-LRU scan with the gated input squeezed through per-row int8."""
    return rglru_scan_ref(a.astype(jnp.float32), _q8_roundtrip(x)).astype(x.dtype)
