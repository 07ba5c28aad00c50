"""Jit'd public wrappers for every Pallas kernel, with CPU fallbacks.

The model code calls THESE (never pallas_call directly).  Each op:
  * dispatches to the Pallas kernel — in the interpreter on the CPU backend,
    compiled on a TPU; callers take ``interpret`` from
    :func:`repro.kernels.interpret_default`, the one place that decides it,
  * exposes a ``use_kernel=False`` escape hatch to the jnp oracle,
  * is differentiable: forward kernels carry a ``jax.custom_vjp`` whose
    backward recomputes through the reference (flash-style recompute — the
    residuals are the INPUTS, not the O(S^2) intermediates).
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import interpret_default  # noqa: F401  (re-exported)
from repro.kernels import ref as R
from repro.kernels.decode_attention import decode_attention as _decode_pallas
from repro.kernels.decode_attention import (
    decode_attention_int8 as _decode_int8_pallas,
)
from repro.kernels.decode_attention import (
    paged_decode_attention as _paged_decode_pallas,
)
from repro.kernels.decode_attention import (
    paged_decode_attention_int8 as _paged_decode_int8_pallas,
)
from repro.kernels.flash_attention import flash_attention_fwd as _flash_pallas
from repro.kernels.flash_attention import (
    flash_attention_int8_fwd as _flash_int8_pallas,
)
from repro.kernels.fused_moe import fused_moe_mlp_fwd as _fused_moe_pallas
from repro.kernels.quantize import dequantize_int8 as _deq
from repro.kernels.quantize import quantize_int8 as _quant_pallas
from repro.kernels.rglru_scan import rglru_scan as _rglru_pallas
from repro.kernels.rglru_scan import rglru_scan_int8 as _rglru_int8_pallas
from repro.kernels.rwkv6_scan import rwkv6_scan as _rwkv6_pallas
from repro.kernels.rwkv6_scan import rwkv6_scan_int8 as _rwkv6_int8_pallas


# ---------------------------------------------------------------------------
# flash attention (differentiable)
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash_attention(q, k, v, causal, window, block, interpret):
    return _flash_pallas(
        q, k, v, causal=causal, window=window,
        block_q=block, block_k=block, interpret=interpret,
    )


def _flash_fwd(q, k, v, causal, window, block, interpret):
    out = _flash_attention(q, k, v, causal, window, block, interpret)
    return out, (q, k, v)


def _flash_bwd(causal, window, block, interpret, res, g):
    q, k, v = res
    # recompute through the oracle; XLA fuses this into a memory-bounded bwd
    _, vjp = jax.vjp(
        lambda q_, k_, v_: R.flash_attention_ref(
            q_, k_, v_, causal=causal, window=window
        ),
        q, k, v,
    )
    return vjp(g)


_flash_attention.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(
    q: jax.Array, k: jax.Array, v: jax.Array,
    *,
    causal: bool = False,
    window: Optional[int] = None,
    block: int = 128,
    interpret: bool = False,
    use_kernel: bool = True,
) -> jax.Array:
    if not use_kernel:
        return R.flash_attention_ref(q, k, v, causal=causal, window=window)
    return _flash_attention(q, k, v, causal, window, block, interpret)


# ---------------------------------------------------------------------------
# causal training attention: the splash kernel pair bundled with JAX
# ---------------------------------------------------------------------------

SPLASH_BLOCKS = (1024, 512, 256, 128)   # largest first; one 128-lane tile is the floor


def _splash_block(seq: int, head_dim: int) -> int:
    """The splash tile for a sequence length (a multiple of the floor): the
    largest of :data:`SPLASH_BLOCKS` that divides it, at most
    1024 x 128 / head_dim (a tile's VMEM grows with the head)."""
    cap = 1024 * 128 // head_dim
    return next(b for b in SPLASH_BLOCKS if b <= cap and seq % b == 0)


def _splash_block_sizes(seq: int, head_dim: int):
    """Forward: square tiles of :func:`_splash_block`, scores computed 512
    keys at a time.  Backward: one fused dq/dkv kernel over 512 queries x a
    whole tile of keys (on a v5e at seq 2048, D 128 this beat separate dq
    and dkv kernels and every 256/512 square tiling; PERF.md)."""
    from jax.experimental.pallas.ops.tpu import splash_attention as SA

    b = _splash_block(seq, head_dim)
    c = min(b, 512 * 128 // head_dim)
    return SA.BlockSizes(
        block_q=b, block_kv=b, block_kv_compute=c,
        block_q_dkv=c, block_kv_dkv=b, block_kv_dkv_compute=c,
        use_fused_bwd_kernel=True,
    )


def splash_causal_attention(
    q: jax.Array, k: jax.Array, v: jax.Array, *, interpret: bool = False,
) -> jax.Array:
    """Causal attention through the splash forward and dq/dkv backward
    kernels. q: (B, S, H, D); k, v: (B, S, Hkv, D) with H % Hkv == 0, S a
    multiple of 128.

    q is pre-scaled by 1/sqrt(D) and rounded to its own dtype; the kernels
    take the operands in that dtype and keep softmax statistics and
    accumulators in f32.  Tiles above the diagonal are skipped (no FLOPs, no
    DMA) forward and backward.  Grouped kv heads are read in place by each
    group of query heads (kv head = query head // (H / Hkv)); nothing is
    repeated in memory.
    """
    from jax.experimental.pallas.ops.tpu import splash_attention as SA

    _, S, H, D = q.shape
    kernel = SA.make_splash_mha(
        SA.MultiHeadMask([SA.CausalMask((S, S))] * H),
        block_sizes=_splash_block_sizes(S, D),
        head_shards=1, q_seq_shards=1, interpret=interpret,
    )
    q = (q.astype(jnp.float32) * (1.0 / D ** 0.5)).astype(q.dtype)
    heads_major = lambda t: jnp.swapaxes(t, 1, 2)     # (B, S, H, D) <-> (B, H, S, D)
    return heads_major(jax.vmap(kernel)(*map(heads_major, (q, k, v))))


# ---------------------------------------------------------------------------
# quantized-training (q8) ops: int8 streamed activations, int8 residuals
# ---------------------------------------------------------------------------
#
# Each q8 op quantizes its big streamed operands per-row to int8 (deterministic
# round-half-up — the Pallas quantize kernel with constant 0.5 noise, pinned
# bit-equal to the oracle), runs the fused kernel that dequantizes tiles
# inside VMEM, and saves the INT8 tensors + scales as the custom-vjp
# residuals — the saved-for-backward pytree shrinks ~4x.  Backward
# dequantizes once and recomputes through the reference (straight-through
# across the rounding, exactly the grad of the base op at the dequantized
# point — what the parity tests pin).


def _q8_quant(x, interpret, use_kernel):
    """Per-row round-half-up int8; Pallas kernel or its bit-equal oracle."""
    if not use_kernel:
        return R.quantize_int8_ref(x, jnp.full(x.shape, 0.5, jnp.float32))
    shp = x.shape
    x2 = x.reshape(-1, shp[-1])
    q, s = _quant_pallas(
        x2, jnp.full(x2.shape, 0.5, jnp.float32), interpret=interpret
    )
    return q.reshape(shp), s.reshape(shp[:-1] + (1,))


def _dtype_tag(x):
    """Zero-size carrier smuggling a primal dtype through vjp residuals."""
    return jnp.zeros((0,), x.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash_q8(q, k, v, causal, window, block, interpret, use_kernel):
    out, _ = _flash_q8_fwd(q, k, v, causal, window, block, interpret, use_kernel)
    return out


def _flash_q8_fwd(q, k, v, causal, window, block, interpret, use_kernel):
    kq, ks = _q8_quant(k, interpret, use_kernel)
    vq, vs = _q8_quant(v, interpret, use_kernel)
    if use_kernel:
        out = _flash_int8_pallas(
            q, kq, ks, vq, vs, causal=causal, window=window,
            block_q=block, block_k=block, interpret=interpret,
        )
    else:
        out = R.flash_attention_ref(
            q, R.dequantize_int8_ref(kq, ks), R.dequantize_int8_ref(vq, vs),
            causal=causal, window=window,
        )
    return out, (q, kq, ks, vq, vs, _dtype_tag(k), _dtype_tag(v))


def _flash_q8_bwd(causal, window, block, interpret, use_kernel, res, g):
    q, kq, ks, vq, vs, ktag, vtag = res
    kd = R.dequantize_int8_ref(kq, ks)
    vd = R.dequantize_int8_ref(vq, vs)
    _, vjp = jax.vjp(
        lambda q_, k_, v_: R.flash_attention_ref(
            q_, k_, v_, causal=causal, window=window
        ).astype(g.dtype),
        q, kd, vd,
    )
    dq, dk, dv = vjp(g)
    return dq.astype(q.dtype), dk.astype(ktag.dtype), dv.astype(vtag.dtype)


_flash_q8.defvjp(_flash_q8_fwd, _flash_q8_bwd)


def flash_attention_q8(
    q: jax.Array, k: jax.Array, v: jax.Array,
    *,
    causal: bool = False,
    window: Optional[int] = None,
    block: int = 128,
    interpret: bool = False,
    use_kernel: bool = True,
) -> jax.Array:
    """Int8-fused training attention: K/V live in int8 end to end.

    K/V are quantized per-row (scale = absmax/127, round-half-up), the
    online-softmax sweep dequantizes each tile inside VMEM with f32
    accumulation, and the backward residuals save the int8 K/V + scales
    instead of the f32 tensors.  ``use_kernel=False`` runs the same math
    off-Pallas (exact fallback)."""
    return _flash_q8(q, k, v, causal, window, block, interpret, use_kernel)


# ---------------------------------------------------------------------------
# decode attention (inference only — no vjp needed)
# ---------------------------------------------------------------------------


def decode_attention(
    q: jax.Array, k: jax.Array, v: jax.Array, valid_len: jax.Array,
    *,
    window: Optional[int] = None,
    block_k: int = 512,
    interpret: bool = False,
    use_kernel: bool = True,
) -> jax.Array:
    if not use_kernel:
        return R.decode_attention_ref(q, k, v, valid_len, window=window)
    return _decode_pallas(
        q, k, v, valid_len, window=window, block_k=block_k, interpret=interpret
    )


def paged_decode_attention(
    q: jax.Array, k_pages: jax.Array, v_pages: jax.Array,
    block_table: jax.Array, valid_len: jax.Array,
    *,
    window: Optional[int] = None,
    interpret: bool = False,
    use_kernel: bool = True,
) -> jax.Array:
    if not use_kernel:
        return R.paged_decode_attention_ref(
            q, k_pages, v_pages, block_table, valid_len, window=window
        )
    return _paged_decode_pallas(
        q, k_pages, v_pages, block_table, valid_len,
        window=window, interpret=interpret,
    )


def decode_attention_int8(
    q: jax.Array, k: jax.Array, k_scale: jax.Array,
    v: jax.Array, v_scale: jax.Array, valid_len: jax.Array,
    *,
    window: Optional[int] = None,
    block_k: int = 512,
    interpret: bool = False,
    use_kernel: bool = True,
) -> jax.Array:
    """Decode over an int8 KV cache (+ per-row f32 scales), dequantized
    inside the kernel — the cache sweep moves ~4x fewer HBM bytes."""
    if not use_kernel:
        return R.decode_attention_int8_ref(
            q, k, k_scale, v, v_scale, valid_len, window=window
        )
    return _decode_int8_pallas(
        q, k, k_scale, v, v_scale, valid_len,
        window=window, block_k=block_k, interpret=interpret,
    )


def paged_decode_attention_int8(
    q: jax.Array, k_pages: jax.Array, k_scales: jax.Array,
    v_pages: jax.Array, v_scales: jax.Array,
    block_table: jax.Array, valid_len: jax.Array,
    *,
    window: Optional[int] = None,
    interpret: bool = False,
    use_kernel: bool = True,
) -> jax.Array:
    """Paged decode over an int8 page pool; see :func:`decode_attention_int8`."""
    if not use_kernel:
        return R.paged_decode_attention_int8_ref(
            q, k_pages, k_scales, v_pages, v_scales, block_table, valid_len,
            window=window,
        )
    return _paged_decode_int8_pallas(
        q, k_pages, k_scales, v_pages, v_scales, block_table, valid_len,
        window=window, interpret=interpret,
    )


# ---------------------------------------------------------------------------
# fused MoE dispatch + expert SwiGLU (differentiable)
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8))
def _fused_moe(x, router, wg, wu, wo, k, capacity, block_c, interpret):
    return _fused_moe_pallas(
        x, router, wg, wu, wo,
        k=k, capacity=capacity, block_c=block_c, interpret=interpret,
    )


def _fused_moe_fwd(x, router, wg, wu, wo, k, capacity, block_c, interpret):
    out = _fused_moe(x, router, wg, wu, wo, k, capacity, block_c, interpret)
    return out, (x, router, wg, wu, wo)


def _fused_moe_bwd(k, capacity, block_c, interpret, res, g):
    x, router, wg, wu, wo = res
    # recompute through the oracle: re-derives routing + dispatch (cheap int
    # ops) and the expert GEMM intermediates rather than saving E*C*f floats
    _, vjp = jax.vjp(
        lambda x_, r_, wg_, wu_, wo_: R.fused_moe_mlp_ref(
            x_, r_, wg_, wu_, wo_, k=k, capacity=capacity
        ),
        x, router, wg, wu, wo,
    )
    return vjp(g)


_fused_moe.defvjp(_fused_moe_fwd, _fused_moe_bwd)


def fused_moe_mlp(
    x: jax.Array,               # (T, d) tokens
    router: jax.Array,          # (d, E)
    wg: jax.Array, wu: jax.Array, wo: jax.Array,  # expert SwiGLU weights
    *,
    k: int,
    capacity: int,
    block_c: int = 128,
    interpret: bool = False,
    use_kernel: bool = True,
) -> Tuple[jax.Array, jax.Array]:
    """Fused top-k MoE layer: routing stays in XLA, dispatch gather + capacity
    mask + expert SwiGLU + gate scaling run in one Pallas kernel.  Returns
    ``(out (T, d), aux_loss)``; backward recomputes through the oracle."""
    if not use_kernel:
        return R.fused_moe_mlp_ref(x, router, wg, wu, wo, k=k, capacity=capacity)
    return _fused_moe(x, router, wg, wu, wo, k, capacity, block_c, interpret)


# ---------------------------------------------------------------------------
# grouped matmul over the experts held (dropless MoE; differentiable)
# ---------------------------------------------------------------------------

GMM_TILE_M = 512            # rows of one grouped-matmul tile on a TPU
_GMM_TILES = (1024, 768, 512, 256, 128)


def _gmm_tiling(m: int, k: int, n: int) -> Tuple[int, int, int]:
    """The TPU kernel's (rows, contraction, output) tile: GMM_TILE_M rows,
    and the largest of :data:`_GMM_TILES` dividing each of k and n."""
    pick = lambda x: next((t for t in _GMM_TILES if x % t == 0), x)
    return GMM_TILE_M, pick(k), pick(n)


def grouped_matmul(
    lhs: jax.Array, rhs: jax.Array, group_sizes: jax.Array,
) -> jax.Array:
    """``lhs`` rows in consecutive groups, group g times ``rhs[g]``.

    lhs (M, K), its rows sorted by group; rhs (G, K, N); group_sizes (G,)
    int32 summing to at most M.  Rows past the last group come out zero.
    Output in lhs's dtype, accumulated in f32.  On a TPU: the megablox
    ``gmm`` kernel (backward: ``gmm`` against the transposed weights for
    lhs, ``tgmm`` for rhs), which visits only the row tiles the groups
    cover, so its work follows sum(group_sizes), not M; inside the training
    step it ran faster than XLA's ragged dot (PERF.md).  Elsewhere
    ``lax.ragged_dot`` (on the CPU it computes every group for every row).
    """
    if jax.default_backend() != "tpu":
        return jax.lax.ragged_dot(lhs, rhs, group_sizes,
                                  preferred_element_type=lhs.dtype)
    from jax.experimental.pallas.ops.tpu.megablox import ops as MB

    m = lhs.shape[0]
    pad = (-m) % GMM_TILE_M
    if pad:
        lhs = jnp.pad(lhs, ((0, pad), (0, 0)))
    # one more group, past the held ones, takes the rows no group covers:
    # the kernel then zeroes them (it leaves uncovered rows unwritten)
    rest = m + pad - jnp.sum(group_sizes)
    sizes = jnp.concatenate([group_sizes, rest[None]]).astype(jnp.int32)
    out = MB.gmm(lhs, rhs, sizes, lhs.dtype, _gmm_tiling, None, None,
                 False, interpret_default())
    return out[:m]


# ---------------------------------------------------------------------------
# RG-LRU scan (differentiable)
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _rglru(a, x, chunk, interpret):
    return _rglru_pallas(a, x, chunk=chunk, interpret=interpret)


def _rglru_fwd(a, x, chunk, interpret):
    y = _rglru(a, x, chunk, interpret)
    return y, (a, x)


def _rglru_bwd(chunk, interpret, res, g):
    a, x = res
    _, vjp = jax.vjp(lambda a_, x_: R.rglru_scan_ref(a_, x_), a, x)
    return vjp(g)


_rglru.defvjp(_rglru_fwd, _rglru_bwd)


def rglru_scan(
    a: jax.Array, x: jax.Array, *,
    chunk: int = 128, interpret: bool = False, use_kernel: bool = True,
) -> jax.Array:
    if not use_kernel:
        return R.rglru_scan_ref(a, x)
    return _rglru(a, x, chunk, interpret)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def _rglru_q8(a, x, chunk, interpret, use_kernel):
    y, _ = _rglru_q8_fwd(a, x, chunk, interpret, use_kernel)
    return y


def _rglru_q8_fwd(a, x, chunk, interpret, use_kernel):
    xq, xs = _q8_quant(x, interpret, use_kernel)
    if use_kernel:
        y = _rglru_int8_pallas(
            a, xq, xs, chunk=chunk, interpret=interpret, out_dtype=x.dtype
        )
    else:
        y = R.rglru_scan_ref(a, R.dequantize_int8_ref(xq, xs)).astype(x.dtype)
    # decay stays f32 (its seq padding must be exactly 1.0); only the gated
    # input rides int8 — it is the larger, freshly-computed activation
    return y, (a, xq, xs, _dtype_tag(x))


def _rglru_q8_bwd(chunk, interpret, use_kernel, res, g):
    a, xq, xs, xtag = res
    xd = R.dequantize_int8_ref(xq, xs)
    _, vjp = jax.vjp(
        lambda a_, x_: R.rglru_scan_ref(a_, x_).astype(g.dtype), a, xd
    )
    da, dx = vjp(g)
    return da.astype(a.dtype), dx.astype(xtag.dtype)


_rglru_q8.defvjp(_rglru_q8_fwd, _rglru_q8_bwd)


def rglru_scan_q8(
    a: jax.Array, x: jax.Array, *,
    chunk: int = 128, interpret: bool = False, use_kernel: bool = True,
) -> jax.Array:
    """Int8-fused RG-LRU: the gated input streams as int8 + per-row scales,
    dequantized inside the scan (f32 carry), and the backward residual saves
    the int8 input instead of the f32 one."""
    return _rglru_q8(a, x, chunk, interpret, use_kernel)


# ---------------------------------------------------------------------------
# RWKV6 scan (differentiable)
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _rwkv6(r, k, v, w, u, chunk, interpret):
    return _rwkv6_pallas(r, k, v, w, u, chunk=chunk, interpret=interpret)


def _rwkv6_fwd(r, k, v, w, u, chunk, interpret):
    out = _rwkv6(r, k, v, w, u, chunk, interpret)
    return out, (r, k, v, w, u)


def _rwkv6_bwd(chunk, interpret, res, g):
    r, k, v, w, u = res
    _, vjp = jax.vjp(
        lambda r_, k_, v_, w_, u_: R.rwkv6_scan_ref(r_, k_, v_, w_, u_),
        r, k, v, w, u,
    )
    return vjp(g)


_rwkv6.defvjp(_rwkv6_fwd, _rwkv6_bwd)


def rwkv6_scan(
    r, k, v, w, u, *,
    chunk: int = 32, interpret: bool = False, use_kernel: bool = True,
) -> Tuple[jax.Array, jax.Array]:
    if not use_kernel:
        return R.rwkv6_scan_ref(r, k, v, w, u)
    return _rwkv6(r, k, v, w, u, chunk, interpret)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _rwkv6_q8(r, k, v, w, u, chunk, interpret, use_kernel):
    out, _ = _rwkv6_q8_fwd(r, k, v, w, u, chunk, interpret, use_kernel)
    return out


def _rwkv6_q8_fwd(r, k, v, w, u, chunk, interpret, use_kernel):
    rq, rs = _q8_quant(r, interpret, use_kernel)
    kq, ks = _q8_quant(k, interpret, use_kernel)
    vq, vs = _q8_quant(v, interpret, use_kernel)
    if use_kernel:
        out, s_fin = _rwkv6_int8_pallas(
            rq, rs, kq, ks, vq, vs, w, u,
            chunk=chunk, interpret=interpret, out_dtype=r.dtype,
        )
    else:
        out, s_fin = R.rwkv6_scan_ref(
            R.dequantize_int8_ref(rq, rs), R.dequantize_int8_ref(kq, ks),
            R.dequantize_int8_ref(vq, vs), w.astype(jnp.float32), u,
        )
        out = out.astype(r.dtype)
    res = (rq, rs, kq, ks, vq, vs, w, u,
           _dtype_tag(r), _dtype_tag(k), _dtype_tag(v))
    return (out, s_fin), res


def _rwkv6_q8_bwd(chunk, interpret, use_kernel, res, g):
    rq, rs, kq, ks, vq, vs, w, u, rtag, ktag, vtag = res
    rd = R.dequantize_int8_ref(rq, rs)
    kd = R.dequantize_int8_ref(kq, ks)
    vd = R.dequantize_int8_ref(vq, vs)
    g_out, g_s = g

    def f(r_, k_, v_, w_, u_):
        o, s = R.rwkv6_scan_ref(r_, k_, v_, w_, u_)
        return o.astype(g_out.dtype), s

    _, vjp = jax.vjp(f, rd, kd, vd, w, u)
    dr, dk, dv, dw, du = vjp((g_out, g_s))
    return (dr.astype(rtag.dtype), dk.astype(ktag.dtype),
            dv.astype(vtag.dtype), dw, du)


_rwkv6_q8.defvjp(_rwkv6_q8_fwd, _rwkv6_q8_bwd)


def rwkv6_scan_q8(
    r, k, v, w, u, *,
    chunk: int = 32, interpret: bool = False, use_kernel: bool = True,
) -> Tuple[jax.Array, jax.Array]:
    """Int8-fused WKV scan: r/k/v stream as int8 + per-row scales with
    in-kernel dequant (decay/bonus stay f32 — the log-space overflow-safety
    math), and the backward residuals save the int8 activations."""
    return _rwkv6_q8(r, k, v, w, u, chunk, interpret, use_kernel)


# ---------------------------------------------------------------------------
# int8 quantize / dequantize
# ---------------------------------------------------------------------------


def quantize_int8(
    x: jax.Array, noise: Optional[jax.Array] = None, *,
    block_rows: int = 256, interpret: bool = False, use_kernel: bool = True,
) -> Tuple[jax.Array, jax.Array]:
    """x: (R, N).  noise None => deterministic nearest rounding (oracle path)."""
    if noise is None or not use_kernel:
        return R.quantize_int8_ref(x, noise)
    return _quant_pallas(x, noise, block_rows=block_rows, interpret=interpret)


dequantize_int8 = _deq
