"""RWKV-6 WKV recurrence as a chunked-parallel Pallas kernel.

The recurrence (per batch, head; state S in R^{DxD}):
    out_t = r_t @ (S_{t-1} + diag(u) k_t v_t^T)
    S_t   = diag(w_t) S_{t-1} + k_t v_t^T

TPU adaptation — chunked linear attention (GLA-style), NOT a token-serial
port: for a chunk of length L with per-channel log-decays lw_t = log w_t and
prefix sums  cum_t = sum_{j<=t} lw_j:

    inter-chunk:  out  = (r_t * exp(cum_{t-1})) @ S_0          (one (L,D)x(D,D) MXU matmul)
    intra-chunk:  A_{t,j} = sum_d r_t[d] k_j[d] exp(cum_{t-1,d} - cum_{j,d}),  j <  t
                  A_{t,t} = sum_d r_t[d] u[d] k_t[d]
                  out += A @ V                                  (one key row j at a time)
    state:        S_L  = diag(exp(cum_L)) S_0 + (k * exp(cum_L - cum))^T @ V

Every exponent above is <= 0 (decays only accumulate), so the chunked form is
overflow-safe WITHOUT the unstable 1/decay factorization a naive CUDA port
would use.  The intra-chunk term loops over the L key rows on 2-D (L, D)
tiles (Mosaic can lay out neither the (L, L, D) pairwise tensor nor
``cumsum``; the prefix sum is a triangular matmul).  The state (D, D) is
carried across chunks in VMEM scratch (sequential innermost grid dim).
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _wkv6_body(
    r, k, v, lw, u, o_ref, sfin_ref,
    s_ref,                    # (D, D) f32 scratch — the carried state
    *,
    L: int,
    n_chunks: int,
):
    """Shared chunked-WKV sweep over already-loaded f32 (L, D) tiles.

    Both the f32 and the int8 (in-kernel dequant) kernels call this; the
    only difference between them is how the r/k/v tiles reach f32."""
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _init():
        s_ref[...] = jnp.zeros_like(s_ref)

    s0 = s_ref[...]                           # (D, D)

    # cum_t = sum_{j<=t} lw_j as a lower-triangular matmul (Mosaic has no
    # cumsum); HIGHEST keeps the f32 sum exact enough for the decay math
    incl = (jax.lax.broadcasted_iota(jnp.int32, (L, L), 0) >=
            jax.lax.broadcasted_iota(jnp.int32, (L, L), 1)).astype(jnp.float32)
    cum = jax.lax.dot_general(                # (L, D)
        incl, lw, (((1,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )
    cum_prev = cum - lw                       # sum_{j<t}

    # inter-chunk: r_t scaled by accumulated decay hits the carried state
    q_eff = r * jnp.exp(cum_prev)             # exponent <= 0
    out = jax.lax.dot_general(
        q_eff, s0, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )                                         # (L, D)

    # intra-chunk, one key row j at a time (2-D tiles only: Mosaic cannot
    # lay out the (L, L, D) pairwise tensor):
    #   A[t, j] = sum_d r_t[d] k_j[d] exp(cum_prev[t, d] - cum[j, d]), j < t
    #   out_t  += A[t, j] v_j
    t_idx = jax.lax.broadcasted_iota(jnp.int32, (L, 1), 0)
    for j in range(L):
        decay = jnp.exp(jnp.minimum(cum_prev - cum[j:j + 1], 0.0))  # <= 0 exp
        a = jnp.sum(r * k[j:j + 1] * decay, axis=1, keepdims=True)   # (L, 1)
        out = out + jnp.where(t_idx > j, a, 0.0) * v[j:j + 1]
    # diagonal bonus: A[t, t] = sum_d r_t[d] u[d] k_t[d]
    out = out + jnp.sum(r * u * k, axis=1, keepdims=True) * v
    o_ref[0, 0] = out.astype(o_ref.dtype)

    # state update: S_L = diag(exp(cum_L)) S0 + (k * exp(cum_L - cum))^T V
    cum_L = cum[L - 1:L]                                   # (1, D)
    k_dec = k * jnp.exp(cum_L - cum)                       # exponent <= 0
    # exp(cum_L) as a (D, 1) column: the diagonal of its row broadcast
    D = s0.shape[0]
    eye = jax.lax.broadcasted_iota(jnp.int32, (D, D), 0) == \
        jax.lax.broadcasted_iota(jnp.int32, (D, D), 1)
    decay_col = jnp.sum(jnp.where(eye, jnp.exp(cum_L), 0.0), axis=1,
                        keepdims=True)
    s_new = decay_col * s0 + jax.lax.dot_general(
        k_dec, v, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    s_ref[...] = s_new

    @pl.when(ic == n_chunks - 1)
    def _emit_state():
        sfin_ref[0, 0] = s_new


def _wkv6_kernel(r_ref, k_ref, v_ref, lw_ref, u_ref, o_ref, sfin_ref, s_ref, **kw):
    _wkv6_body(
        r_ref[0, 0].astype(jnp.float32),
        k_ref[0, 0].astype(jnp.float32),
        v_ref[0, 0].astype(jnp.float32),
        lw_ref[0, 0].astype(jnp.float32),
        u_ref[0].astype(jnp.float32),
        o_ref, sfin_ref, s_ref, **kw,
    )


def _wkv6_int8_kernel(
    r_ref, rs_ref, k_ref, ks_ref, v_ref, vs_ref, lw_ref, u_ref,
    o_ref, sfin_ref, s_ref, **kw,
):
    # int8 r/k/v tiles + (L, 1) per-row scales on the same index map; the
    # decay stays f32 (its log-cumsum is the numerically fragile part).
    # The recurrent state is f32 VMEM scratch either way — only the streamed
    # activations are narrow.
    _wkv6_body(
        r_ref[0, 0].astype(jnp.float32) * rs_ref[0, 0],
        k_ref[0, 0].astype(jnp.float32) * ks_ref[0, 0],
        v_ref[0, 0].astype(jnp.float32) * vs_ref[0, 0],
        lw_ref[0, 0].astype(jnp.float32),
        u_ref[0].astype(jnp.float32),
        o_ref, sfin_ref, s_ref, **kw,
    )


def rwkv6_scan(
    r: jax.Array, k: jax.Array, v: jax.Array, w: jax.Array,  # (B, S, H, D)
    u: jax.Array,                                            # (H, D)
    *,
    chunk: int = 32,
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """Returns (out (B, S, H, D), final_state (B, H, D, D))."""
    B, S, H, D = r.shape
    L = min(chunk, S)
    pad = (-S) % L
    # log-decay; padded steps get lw = 0 (w = 1: state passes through).
    # Floor 1e-30 (NOT 1e-38: that is subnormal in f32 and XLA's flush-to-zero
    # turns it into log(0) = -inf); e^-69 per step is already total decay.
    lw = jnp.log(jnp.maximum(w.astype(jnp.float32), 1e-30))
    rt = jnp.moveaxis(r, 2, 1)        # (B, H, S, D)
    kt = jnp.moveaxis(k, 2, 1)
    vt = jnp.moveaxis(v, 2, 1)
    lwt = jnp.moveaxis(lw, 2, 1)
    if pad:
        cfg = ((0, 0), (0, 0), (0, pad), (0, 0))
        rt, kt, vt = (jnp.pad(t, cfg) for t in (rt, kt, vt))
        lwt = jnp.pad(lwt, cfg)       # zeros: w = 1 pass-through
    Sp = rt.shape[2]
    n_chunks = Sp // L

    grid = (B, H, n_chunks)
    out, s_fin = pl.pallas_call(
        functools.partial(_wkv6_kernel, L=L, n_chunks=n_chunks),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, L, D), lambda b, h, ic: (b, h, ic, 0)),
            pl.BlockSpec((1, 1, L, D), lambda b, h, ic: (b, h, ic, 0)),
            pl.BlockSpec((1, 1, L, D), lambda b, h, ic: (b, h, ic, 0)),
            pl.BlockSpec((1, 1, L, D), lambda b, h, ic: (b, h, ic, 0)),
            pl.BlockSpec((1, 1, D), lambda b, h, ic: (h, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, L, D), lambda b, h, ic: (b, h, ic, 0)),
            pl.BlockSpec((1, 1, D, D), lambda b, h, ic: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, Sp, D), r.dtype),
            jax.ShapeDtypeStruct((B, H, D, D), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((D, D), jnp.float32)],
        interpret=interpret,
    )(rt, kt, vt, lwt, u.reshape(H, 1, D))
    out = jnp.moveaxis(out, 1, 2)[:, :S]
    return out, s_fin


def rwkv6_scan_int8(
    r: jax.Array, r_scale: jax.Array,         # (B, S, H, D) int8 / (B, S, H, 1) f32
    k: jax.Array, k_scale: jax.Array,
    v: jax.Array, v_scale: jax.Array,
    w: jax.Array,                             # (B, S, H, D) float decay
    u: jax.Array,                             # (H, D)
    *,
    chunk: int = 32,
    interpret: bool = False,
    out_dtype=jnp.float32,
) -> Tuple[jax.Array, jax.Array]:
    """WKV scan over int8 r/k/v with in-kernel dequantization.

    Identical grid/blocking to :func:`rwkv6_scan`; each (L, D) activation
    tile arrives with its (L, 1) row scales on the same index map and is
    dequantized as it enters the sweep.  Decay/bonus stay f32 — their
    log-space math is the overflow-safety argument — and the (D, D) state
    scratch is f32 as always."""
    B, S, H, D = r.shape
    assert r.dtype == jnp.int8 and k.dtype == jnp.int8 and v.dtype == jnp.int8
    L = min(chunk, S)
    pad = (-S) % L
    lw = jnp.log(jnp.maximum(w.astype(jnp.float32), 1e-30))
    rt, kt, vt = (jnp.moveaxis(t, 2, 1) for t in (r, k, v))
    rst, kst, vst = (jnp.moveaxis(t, 2, 1) for t in (r_scale, k_scale, v_scale))
    lwt = jnp.moveaxis(lw, 2, 1)
    if pad:
        cfg = ((0, 0), (0, 0), (0, pad), (0, 0))
        rt, kt, vt = (jnp.pad(t, cfg) for t in (rt, kt, vt))
        # zero scales: padded steps dequantize to 0 (and lw = 0 passes the
        # state through), so padding cannot perturb the carried state
        rst, kst, vst = (jnp.pad(t, cfg) for t in (rst, kst, vst))
        lwt = jnp.pad(lwt, cfg)
    Sp = rt.shape[2]
    n_chunks = Sp // L

    act_spec = pl.BlockSpec((1, 1, L, D), lambda b, h, ic: (b, h, ic, 0))
    sc_spec = pl.BlockSpec((1, 1, L, 1), lambda b, h, ic: (b, h, ic, 0))
    out, s_fin = pl.pallas_call(
        functools.partial(_wkv6_int8_kernel, L=L, n_chunks=n_chunks),
        grid=(B, H, n_chunks),
        in_specs=[
            act_spec, sc_spec, act_spec, sc_spec, act_spec, sc_spec,
            act_spec,
            pl.BlockSpec((1, 1, D), lambda b, h, ic: (h, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, L, D), lambda b, h, ic: (b, h, ic, 0)),
            pl.BlockSpec((1, 1, D, D), lambda b, h, ic: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, Sp, D), out_dtype),
            jax.ShapeDtypeStruct((B, H, D, D), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((D, D), jnp.float32)],
        interpret=interpret,
    )(rt, rst, kt, kst, vt, vst, lwt, u.reshape(H, 1, D))
    out = jnp.moveaxis(out, 1, 2)[:, :S]
    return out, s_fin
