"""Shared transformer layers: norms, RoPE / M-RoPE, GQA attention (full / causal /
local-window / cross, train + KV-cache decode), MLPs, embeddings.

All functions are pure; parameters come in as nested dicts created by
:mod:`repro.models.param`.  Activation sharding is annotated with logical axis
names (see :mod:`repro.distributed.sharding`).
"""
from __future__ import annotations

import contextlib
import dataclasses
import math
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.compat import get_abstract_mesh
from repro.distributed.sharding import mesh_spec
from repro.distributed.sharding import with_logical_constraint as wlc
from repro.models.param import (
    ParamBuilder,
    normal_init,
    ones_init,
    scaled_init,
    zeros_init,
)

# ---------------------------------------------------------------------------
# Global compute switches
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class ComputeFlags:
    use_pallas: bool = False          # dispatch attention/scan hot spots to kernels
    attn_dtype: Any = jnp.float32     # accumulation dtype for attention softmax
    # off the TPU training route (other backends, non-causal or windowed
    # attention, prefill): switch to the chunked (flash-style,
    # O(S·chunk)-memory) XLA attention path when Sq*Skv exceeds this; the
    # exact sdpa stays the small-shape oracle.  Sequences past 1024 go
    # chunked: exact scores for 8 x 2048 tokens at 32 heads are 4 GiB of f32,
    # more than a v5e's step can spare.
    chunk_threshold: int = 1024 * 1024
    attn_chunk: int = 512             # KV chunk length for the chunked path


FLAGS = ComputeFlags()


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------


def init_rmsnorm(b: ParamBuilder, name: str, dim: int):
    s = b.scope(name)
    s.param("scale", (dim,), ("norm",), init=ones_init())


def rms_norm(p: Dict, x: jax.Array, eps: float = 1e-6) -> jax.Array:
    dt = x.dtype
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    return (y * p["scale"].astype(jnp.float32)).astype(dt)


def init_layernorm(b: ParamBuilder, name: str, dim: int):
    s = b.scope(name)
    s.param("scale", (dim,), ("norm",), init=ones_init())
    s.param("bias", (dim,), ("norm",), init=zeros_init())


def layer_norm(p: Dict, x: jax.Array, eps: float = 1e-5) -> jax.Array:
    dt = x.dtype
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean((xf - mu) ** 2, axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (y * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32)).astype(dt)


# ---------------------------------------------------------------------------
# RoPE (standard and multimodal M-RoPE)
# ---------------------------------------------------------------------------


def rope_freqs(head_dim: int, theta: float = 10000.0) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float = 10000.0) -> jax.Array:
    """x: (..., S, H, D); positions: broadcastable to (..., S)."""
    d = x.shape[-1]
    freqs = rope_freqs(d, theta)                       # (D/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., S, D/2)
    angles = angles[..., None, :]                      # (..., S, 1, D/2)
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = x[..., : d // 2], x[..., d // 2 :]
    out = jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
    )
    return out.astype(x.dtype)


def apply_mrope(
    x: jax.Array,
    positions: jax.Array,
    sections: Tuple[int, ...],
    theta: float = 1000000.0,
) -> jax.Array:
    """Qwen2-VL multimodal RoPE.

    x: (B, S, H, D); positions: (3, B, S) — (temporal, height, width) position ids.
    ``sections`` gives the number of *frequency pairs* per modality,
    sum(sections) == D/2.  Each frequency pair i uses the position stream of the
    section it falls into.
    """
    d = x.shape[-1]
    assert sum(sections) == d // 2, (sections, d)
    freqs = rope_freqs(d, theta)                       # (D/2,)
    # section id per frequency pair
    sec_ids = jnp.repeat(
        jnp.arange(len(sections)), jnp.array(sections), total_repeat_length=d // 2
    )                                                  # (D/2,)
    # pos_per_freq: (B, S, D/2) — pick the position stream per pair
    pos = jnp.moveaxis(positions, 0, -1)               # (B, S, 3)
    pos_per_freq = jnp.take_along_axis(
        pos.astype(jnp.float32),
        jnp.broadcast_to(sec_ids, pos.shape[:-1] + (d // 2,)).astype(jnp.int32),
        axis=-1,
    )                                                  # (B, S, D/2)
    angles = pos_per_freq[..., None, :] * freqs        # (B, S, 1, D/2)
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = x[..., : d // 2], x[..., d // 2 :]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Attention (GQA) — parameters
# ---------------------------------------------------------------------------


def init_attention(
    b: ParamBuilder,
    name: str,
    d_model: int,
    n_heads: int,
    n_kv_heads: int,
    head_dim: int,
    qkv_bias: bool = False,
    qk_norm: bool = False,
):
    s = b.scope(name)
    s.param("wq", (d_model, n_heads, head_dim), ("embed", "heads", "head_dim"),
            init=scaled_init(0))
    s.param("wk", (d_model, n_kv_heads, head_dim), ("embed", "kv_heads", "head_dim"),
            init=scaled_init(0))
    s.param("wv", (d_model, n_kv_heads, head_dim), ("embed", "kv_heads", "head_dim"),
            init=scaled_init(0))
    s.param("wo", (n_heads, head_dim, d_model), ("heads", "head_dim", "embed"),
            init=scaled_init(0))
    if qkv_bias:
        s.param("bq", (n_heads, head_dim), ("heads", "head_dim"), init=zeros_init())
        s.param("bk", (n_kv_heads, head_dim), ("kv_heads", "head_dim"), init=zeros_init())
        s.param("bv", (n_kv_heads, head_dim), ("kv_heads", "head_dim"), init=zeros_init())
    if qk_norm:
        init_rmsnorm(s, "q_norm", head_dim)
        init_rmsnorm(s, "k_norm", head_dim)


def qkv_project(
    p: Dict, x: jax.Array
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """q, k, v heads of ``x``; with QK-norm params, q and k are RMS-normed
    per head over head_dim (one scale shared by the heads), before RoPE."""
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(x.dtype))
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"].astype(x.dtype))
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"].astype(x.dtype))
    if "bq" in p:
        q = q + p["bq"].astype(x.dtype)
        k = k + p["bk"].astype(x.dtype)
        v = v + p["bv"].astype(x.dtype)
    if "q_norm" in p:
        q = rms_norm(p["q_norm"], q)
        k = rms_norm(p["k_norm"], k)
    q = wlc(q, "batch", "seq", "act_heads", None)
    k = wlc(k, "batch", "seq", "act_kv_heads", None)
    v = wlc(v, "batch", "seq", "act_kv_heads", None)
    return q, k, v


def out_project(p: Dict, o: jax.Array) -> jax.Array:
    y = jnp.einsum("bshk,hkd->bsd", o, p["wo"].astype(o.dtype))
    return wlc(y, "batch", "seq", "act_embed")


# ---------------------------------------------------------------------------
# Attention — core math (reference XLA path; Pallas path lives in repro.kernels)
# ---------------------------------------------------------------------------


def _repeat_kv(k: jax.Array, n_heads: int) -> jax.Array:
    """(B, S, Hkv, D) -> (B, S, H, D) by repeating groups."""
    n_kv = k.shape[2]
    if n_kv == n_heads:
        return k
    reps = n_heads // n_kv
    return jnp.repeat(k, reps, axis=2)


def sdpa(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = False,
    window: Optional[int] = None,
    q_offset: int | jax.Array = 0,
    kv_valid_len: Optional[jax.Array] = None,
    softcap: Optional[float] = None,
) -> jax.Array:
    """Reference scaled-dot-product attention with GQA.

    q: (B, Sq, H, D); k, v: (B, Skv, Hkv, D).
    ``q_offset``: absolute position of q[0] within the kv sequence (decode).
    ``window``: local attention window (keys within [pos-window+1, pos]).
    ``kv_valid_len``: (B,) number of valid kv positions (decode with cache).
    """
    B, Sq, H, D = q.shape
    Skv = k.shape[1]
    k = _repeat_kv(k, H)
    v = _repeat_kv(v, H)
    scale = 1.0 / math.sqrt(D)
    logits = jnp.einsum(
        "bqhd,bkhd->bhqk", q.astype(FLAGS.attn_dtype), k.astype(FLAGS.attn_dtype)
    ) * scale
    if softcap is not None:
        logits = jnp.tanh(logits / softcap) * softcap

    q_pos = jnp.arange(Sq) + q_offset           # (Sq,)
    k_pos = jnp.arange(Skv)                     # (Skv,)
    mask = jnp.ones((Sq, Skv), dtype=bool)
    if causal:
        mask &= k_pos[None, :] <= q_pos[:, None]
    if window is not None:
        mask &= k_pos[None, :] > (q_pos[:, None] - window)
    logits = jnp.where(mask[None, None], logits, -1e30)
    if kv_valid_len is not None:
        vmask = k_pos[None, :] < kv_valid_len[:, None]  # (B, Skv)
        logits = jnp.where(vmask[:, None, None, :], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs, v)
    return out


def chunked_sdpa(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = False,
    window: Optional[int] = None,
    q_offset: int | jax.Array = 0,
    softcap: Optional[float] = None,
    chunk: Optional[int] = None,
) -> jax.Array:
    """Flash-style online-softmax attention over KV chunks (pure XLA).

    Memory is O(B·H·Sq·chunk) instead of O(B·H·Sq·Skv).  The long-sequence
    path off the TPU training route (:func:`splash_route`): other backends,
    non-causal and windowed attention, and prefill.  Every chunk is computed
    for every query (no causal skip) on f32 scores.  Numerically matches
    :func:`sdpa` (property-tested).
    """
    B, Sq, H, D = q.shape
    Skv = k.shape[1]
    chunk = chunk or FLAGS.attn_chunk
    chunk = min(chunk, Skv)
    k = _repeat_kv(k, H)
    v = _repeat_kv(v, H)

    pad = (-Skv) % chunk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    n_chunks = (Skv + pad) // chunk

    scale = 1.0 / math.sqrt(D)
    qf = q.astype(FLAGS.attn_dtype) * scale
    q_pos = jnp.arange(Sq) + q_offset                     # (Sq,)

    # xs: (n_chunks, B, chunk, H, D)
    ks = jnp.moveaxis(k.reshape(B, n_chunks, chunk, H, D), 1, 0)
    vs = jnp.moveaxis(v.reshape(B, n_chunks, chunk, H, D), 1, 0)
    chunk_ids = jnp.arange(n_chunks)

    m0 = jnp.full((B, H, Sq), -jnp.inf, FLAGS.attn_dtype)
    l0 = jnp.zeros((B, H, Sq), FLAGS.attn_dtype)
    acc0 = jnp.zeros((B, Sq, H, D), FLAGS.attn_dtype)

    # carry m is updated via m_new; rebind for scan correctness
    def scan_body(carry, xs):
        m, l, acc = carry
        (m2, l2, acc2), _ = _chunk_step(m, l, acc, xs)
        return (m2, l2, acc2), None

    def _chunk_step(m, l, acc, xs):
        kc, vc, ci = xs
        k_pos = ci * chunk + jnp.arange(chunk)
        logits = jnp.einsum("bqhd,bkhd->bhqk", qf, kc.astype(FLAGS.attn_dtype))
        if softcap is not None:
            logits = jnp.tanh(logits / softcap) * softcap
        mask = k_pos[None, :] < Skv
        if causal:
            mask = mask & (k_pos[None, :] <= q_pos[:, None])
        if window is not None:
            mask = mask & (k_pos[None, :] > (q_pos[:, None] - window))
        logits = jnp.where(mask[None, None], logits, -1e30)
        m_new = jnp.maximum(m, jnp.max(logits, axis=-1))
        p = jnp.exp(logits - m_new[..., None])
        p = jnp.where(mask[None, None], p, 0.0)
        corr = jnp.exp(m - m_new)
        corr = jnp.where(jnp.isfinite(corr), corr, 0.0)
        l2 = l * corr + jnp.sum(p, axis=-1)
        pv = jnp.einsum("bhqk,bkhd->bqhd", p, vc.astype(FLAGS.attn_dtype))
        acc2 = acc * jnp.moveaxis(corr, 1, 2)[..., None] + pv
        return (m_new, l2, acc2), None

    # checkpointed body: autodiff keeps only the (m, l, acc) carries per
    # chunk, not each chunk's (B, H, Sq, chunk) scores, so training memory
    # stays O(B·H·Sq·chunk) too
    (m, l, acc), _ = jax.lax.scan(
        jax.checkpoint(scan_body), (m0, l0, acc0), (ks, vs, chunk_ids)
    )
    l = jnp.maximum(l, 1e-30)
    out = acc / jnp.moveaxis(l, 1, 2)[..., None]
    return out.astype(v.dtype)


def _dispatch_path(q: jax.Array, k: jax.Array) -> str:
    """Pallas flash / chunked / exact attention, by flags and problem size."""
    if FLAGS.use_pallas:
        return "flash"
    if q.shape[1] * k.shape[1] > FLAGS.chunk_threshold:
        return "chunked"
    return "exact"


def _dispatch_attention(
    q: jax.Array, k: jax.Array, v: jax.Array, *, causal: bool,
    window: Optional[int], softcap: Optional[float] = None,
) -> jax.Array:
    path = _dispatch_path(q, k)
    if path == "flash":
        from repro.kernels import ops as kops

        return kops.flash_attention(
            q, k, v, causal=causal, window=window,
            interpret=kops.interpret_default(),
        )
    if path == "chunked":
        return chunked_sdpa(q, k, v, causal=causal, window=window, softcap=softcap)
    return sdpa(q, k, v, causal=causal, window=window, softcap=softcap)


# ---------------------------------------------------------------------------
# Attention — the TPU training route (splash forward + dq/dkv backward)
# ---------------------------------------------------------------------------


def _splash_specs(mesh):
    """q and k/v specs that split the kernel's rows over the mesh axes the
    rules give ``batch`` and its heads over those of ``act_heads``."""
    return (mesh_spec(mesh, "batch", "seq", "act_heads", None),
            mesh_spec(mesh, "batch", "seq", "act_kv_heads", None))


def _splash_fits_mesh(q_shape, k_shape) -> bool:
    """No mesh, or one that splits neither sequence and divides the rows and
    heads evenly."""
    mesh = get_abstract_mesh()
    if mesh is None:
        return True
    for spec, shape in zip(_splash_specs(mesh), (q_shape, k_shape)):
        parts = tuple(spec) + (None,) * (len(shape) - len(spec))
        if parts[1] is not None:
            return False
        for part, n in zip(parts, shape):
            axes = () if part is None else (part,) if isinstance(part, str) else part
            if n % math.prod(mesh.shape[a] for a in axes):
                return False
    return True


def splash_route(
    backend: str, q_shape, k_shape, *, causal: bool, window: Optional[int],
    softcap: Optional[float] = None,
) -> bool:
    """Whether training attention runs the fused splash kernel pair: on a TPU,
    causal, no window or softcap, Sq == Skv a multiple of the smallest splash
    tile, whole 128-lane heads, query heads a multiple of kv heads, and rows
    and heads dividing evenly over the mesh."""
    from repro.kernels.ops import SPLASH_BLOCKS

    _, s, h, d = q_shape
    return (
        backend == "tpu" and causal and window is None and softcap is None
        and k_shape[1] == s and s % SPLASH_BLOCKS[-1] == 0
        and d % 128 == 0 and h % k_shape[2] == 0
        and _splash_fits_mesh(q_shape, k_shape)
    )


def splash_attention(q: jax.Array, k: jax.Array, v: jax.Array) -> jax.Array:
    """Causal attention through :func:`repro.kernels.ops.splash_causal_attention`,
    one call per chip under ``shard_map`` (XLA cannot partition the kernel),
    so each chip runs its own rows and heads and nothing is gathered."""
    from repro.kernels import ops as kops

    core = partial(kops.splash_causal_attention,
                   interpret=kops.interpret_default())
    mesh = get_abstract_mesh()
    if mesh is None:
        return core(q, k, v)
    q_spec, kv_spec = _splash_specs(mesh)
    return jax.shard_map(
        core, mesh=mesh, in_specs=(q_spec, kv_spec, kv_spec),
        out_specs=q_spec, check_vma=False,
    )(q, k, v)


# Trace-time tally of the path each attention_train call takes ("splash",
# "chunked", "exact", "flash", "q8"); Session exposes it as attention_paths.
_PATH_TALLIES: list = []
_LAYER_REPEATS = [1]


@contextlib.contextmanager
def attention_path_tally():
    """Count, into the yielded dict, the paths of attention_train calls
    traced inside (a scanned layer stack counts once per layer)."""
    tally: Dict[str, int] = {}
    _PATH_TALLIES.append(tally)
    try:
        yield tally
    finally:
        _PATH_TALLIES.remove(tally)


@contextlib.contextmanager
def repeated_layers(n: int):
    """Each attention_train call traced inside stands for ``n`` layers: the
    body of a ``lax.scan`` over a stack of ``n`` layers is traced once."""
    _LAYER_REPEATS.append(_LAYER_REPEATS[-1] * n)
    try:
        yield
    finally:
        _LAYER_REPEATS.pop()


def _record_path(path: str) -> None:
    for tally in _PATH_TALLIES:
        tally[path] = tally.get(path, 0) + _LAYER_REPEATS[-1]


def attention_train(
    p: Dict,
    x: jax.Array,
    *,
    positions: jax.Array,
    causal: bool = True,
    window: Optional[int] = None,
    rope_theta: float = 10000.0,
    use_rope: bool = True,
    mrope_sections: Optional[Tuple[int, ...]] = None,
    mrope_positions: Optional[jax.Array] = None,
    precision: str = "f32",
) -> jax.Array:
    """Full-sequence attention (training / prefill without cache return).

    ``precision`` is ``ModelConfig.train_precision``: ``"bf16"`` casts the
    attention operands before the kernel; ``"int8-fused"`` routes to the
    quantized-K/V kernel whose backward saves int8 residuals.  The precision
    semantics hold on AND off Pallas (the q8 op has an exact jnp fallback),
    so a trajectory trained on CPU matches the TPU quantization decisions.
    Otherwise, on a TPU, causal attention over whole tiles takes the splash
    kernel pair (:func:`splash_route`); everything else keeps
    :func:`_dispatch_attention`.  Each call adds its path to the active
    :func:`attention_path_tally`.
    """
    q, k, v = qkv_project(p, x)
    if mrope_sections is not None:
        q = apply_mrope(q, mrope_positions, mrope_sections, rope_theta)
        k = apply_mrope(k, mrope_positions, mrope_sections, rope_theta)
    elif use_rope:
        q = apply_rope(q, positions, rope_theta)
        k = apply_rope(k, positions, rope_theta)
    if precision == "bf16":
        q, k, v = (t.astype(jnp.bfloat16) for t in (q, k, v))
    if precision == "int8-fused":
        from repro.kernels import ops as kops

        _record_path("q8")
        o = kops.flash_attention_q8(
            q, k, v, causal=causal, window=window,
            interpret=kops.interpret_default(), use_kernel=FLAGS.use_pallas,
        )
    elif splash_route(jax.default_backend(), q.shape, k.shape,
                      causal=causal, window=window):
        _record_path("splash")
        o = splash_attention(q, k, v)
    else:
        _record_path(_dispatch_path(q, k))
        o = _dispatch_attention(q, k, v, causal=causal, window=window)
    return out_project(p, o.astype(x.dtype))


def attention_prefill(
    p: Dict,
    x: jax.Array,
    *,
    positions: jax.Array,
    cache_len: int,
    causal: bool = True,
    window: Optional[int] = None,
    rope_theta: float = 10000.0,
    use_rope: bool = True,
    rotating: bool = False,
    mrope_sections: Optional[Tuple[int, ...]] = None,
    mrope_positions: Optional[jax.Array] = None,
    kv_cache_dtype: str = "native",
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Prefill: run full attention AND return a KV cache padded to ``cache_len``.

    ``rotating=True`` (local-attention archs): the cache holds only the LAST
    ``min(S, cache_len)`` positions, aligned to slot 0 — the layout the
    rotating-window decode path expects.  Keys keep their absolute RoPE
    phases (RoPE is relative, so rolled slots stay exact).

    ``kv_cache_dtype="int8"``: the returned cache stores per-row symmetric
    int8 K/V + f32 scales (``k_scale``/``v_scale`` leaves); the decode path
    dequantizes inside the kernel.  Attention over the prompt itself still
    runs full-precision — only the cache is quantized.
    """
    q, k, v = qkv_project(p, x)
    if mrope_sections is not None:
        q = apply_mrope(q, mrope_positions, mrope_sections, rope_theta)
        k = apply_mrope(k, mrope_positions, mrope_sections, rope_theta)
    elif use_rope:
        q = apply_rope(q, positions, rope_theta)
        k = apply_rope(k, positions, rope_theta)
    o = _dispatch_attention(q, k, v, causal=causal, window=window)
    B, S, Hkv, D = k.shape
    if rotating and S > cache_len:
        k = k[:, S - cache_len:]
        v = v[:, S - cache_len:]
        S = cache_len
    pad = [(0, 0), (0, cache_len - S), (0, 0), (0, 0)]
    kc, vc = jnp.pad(k, pad), jnp.pad(v, pad)
    if kv_cache_dtype == "int8":
        # padded rows quantize against absmax 0 -> scale floor, q == 0
        from repro.kernels import ref as KR

        kq, ks = KR.quantize_int8_ref(kc)
        vq, vs = KR.quantize_int8_ref(vc)
        cache = {
            "k": wlc(kq, "batch", "kv_seq", "act_kv_heads", None),
            "k_scale": wlc(ks, "batch", "kv_seq", "act_kv_heads", None),
            "v": wlc(vq, "batch", "kv_seq", "act_kv_heads", None),
            "v_scale": wlc(vs, "batch", "kv_seq", "act_kv_heads", None),
        }
    else:
        cache = {
            "k": wlc(kc, "batch", "kv_seq", "act_kv_heads", None),
            "v": wlc(vc, "batch", "kv_seq", "act_kv_heads", None),
        }
    return out_project(p, o), cache


def attention_decode(
    p: Dict,
    x: jax.Array,
    cache: Dict[str, jax.Array],
    *,
    pos: jax.Array,  # (B,) current absolute position of the new token
    window: Optional[int] = None,
    rope_theta: float = 10000.0,
    use_rope: bool = True,
    slot: Optional[jax.Array] = None,        # (B,) cache row to write (default pos)
    valid_len: Optional[jax.Array] = None,   # (B,) valid cache rows (default pos+1)
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """One-token decode against a KV cache. x: (B, 1, d).

    RoPE always uses the ABSOLUTE ``pos`` (never the cache slot): RoPE is
    relative, so as long as every cached key kept its absolute phase, rolled
    rotating-window slots still attend at the true distances.

    An int8 cache (``"k_scale"`` leaf present) is detected from the pytree:
    the new row is quantized per-(batch, head) before the cache write and the
    sweep dequantizes in-kernel (Pallas) or up-front (exact CPU path).
    """
    q, k, v = qkv_project(p, x)                       # (B,1,H,D) / (B,1,Hkv,D)
    if use_rope:
        q = apply_rope(q, pos[:, None], rope_theta)
        k = apply_rope(k, pos[:, None], rope_theta)
    idx = (pos if slot is None else slot).astype(jnp.int32)   # (B,) write row
    valid = (idx + 1) if valid_len is None else valid_len.astype(jnp.int32)
    upd = jax.vmap(lambda c, n, i: jax.lax.dynamic_update_slice(c, n, (i, 0, 0)))
    kv_axes = ("batch", "kv_seq", "act_kv_heads", None)

    if "k_scale" in cache:
        from repro.kernels import ref as KR

        kq, ks_new = KR.quantize_int8_ref(k[:, 0:1])
        vq, vs_new = KR.quantize_int8_ref(v[:, 0:1])
        ck = wlc(upd(cache["k"], kq, idx), *kv_axes)
        cks = wlc(upd(cache["k_scale"], ks_new, idx), *kv_axes)
        cv = wlc(upd(cache["v"], vq, idx), *kv_axes)
        cvs = wlc(upd(cache["v_scale"], vs_new, idx), *kv_axes)
        if FLAGS.use_pallas:
            from repro.kernels import ops as kops

            o = kops.decode_attention_int8(
                q, ck, cks, cv, cvs, valid,
                window=window, interpret=kops.interpret_default(),
            )
        else:
            o = _decode_sdpa_exact(
                q,
                KR.dequantize_int8_ref(ck, cks),
                KR.dequantize_int8_ref(cv, cvs),
                valid - 1, window,
            )
        return out_project(p, o), {
            "k": ck, "k_scale": cks, "v": cv, "v_scale": cvs
        }

    ck = wlc(upd(cache["k"], k[:, 0:1], idx), *kv_axes)
    cv = wlc(upd(cache["v"], v[:, 0:1], idx), *kv_axes)
    if FLAGS.use_pallas:
        from repro.kernels import ops as kops

        o = kops.decode_attention(
            q, ck, cv, valid, window=window, interpret=kops.interpret_default()
        )
    else:
        o = _decode_sdpa_exact(q, ck, cv, valid - 1, window)
    return out_project(p, o), {"k": ck, "v": cv}


def _decode_sdpa_exact(
    q: jax.Array, ck: jax.Array, cv: jax.Array, idx: jax.Array,
    window: Optional[int],
) -> jax.Array:
    """Exact reference decode attention with per-batch positions."""
    B, _, H, D = q.shape
    Skv = ck.shape[1]
    k = _repeat_kv(ck, H)
    v = _repeat_kv(cv, H)
    scale = 1.0 / math.sqrt(D)
    logits = jnp.einsum(
        "bqhd,bkhd->bhqk", q.astype(FLAGS.attn_dtype), k.astype(FLAGS.attn_dtype)
    ) * scale                                        # (B,H,1,Skv)
    k_pos = jnp.arange(Skv)[None, :]                 # (1,Skv)
    mask = k_pos <= idx[:, None]                     # causal-valid
    if window is not None:
        mask &= k_pos > (idx[:, None] - window)
    logits = jnp.where(mask[:, None, None, :], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def cross_attention(
    p: Dict,
    x: jax.Array,
    ctx_k: jax.Array,
    ctx_v: jax.Array,
) -> jax.Array:
    """Decoder cross-attention against precomputed encoder K/V (no RoPE)."""
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(x.dtype))
    if "bq" in p:
        q = q + p["bq"].astype(x.dtype)
    q = wlc(q, "batch", "seq", "act_heads", None)
    o = sdpa(q, ctx_k, ctx_v, causal=False)
    return out_project(p, o)


def cross_kv(p: Dict, ctx: jax.Array) -> Tuple[jax.Array, jax.Array]:
    k = jnp.einsum("bsd,dhk->bshk", ctx, p["wk"].astype(ctx.dtype))
    v = jnp.einsum("bsd,dhk->bshk", ctx, p["wv"].astype(ctx.dtype))
    if "bk" in p:
        k = k + p["bk"].astype(ctx.dtype)
        v = v + p["bv"].astype(ctx.dtype)
    return k, v


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------


def init_swiglu(b: ParamBuilder, name: str, d_model: int, d_ff: int):
    s = b.scope(name)
    s.param("wi_gate", (d_model, d_ff), ("embed", "mlp"), init=scaled_init(0))
    s.param("wi_up", (d_model, d_ff), ("embed", "mlp"), init=scaled_init(0))
    s.param("wo", (d_ff, d_model), ("mlp", "embed"), init=scaled_init(0))


def swiglu(p: Dict, x: jax.Array) -> jax.Array:
    g = jnp.einsum("bsd,df->bsf", x, p["wi_gate"].astype(x.dtype))
    u = jnp.einsum("bsd,df->bsf", x, p["wi_up"].astype(x.dtype))
    h = jax.nn.silu(g) * u
    h = wlc(h, "batch", "seq", "act_mlp")
    y = jnp.einsum("bsf,fd->bsd", h, p["wo"].astype(x.dtype))
    return wlc(y, "batch", "seq", "act_embed")


def init_gelu_mlp(b: ParamBuilder, name: str, d_model: int, d_ff: int, bias: bool = True):
    s = b.scope(name)
    s.param("wi", (d_model, d_ff), ("embed", "mlp"), init=scaled_init(0))
    s.param("wo", (d_ff, d_model), ("mlp", "embed"), init=scaled_init(0))
    if bias:
        s.param("bi", (d_ff,), ("mlp",), init=zeros_init())
        s.param("bo", (d_model,), ("embed",), init=zeros_init())


def gelu_mlp(p: Dict, x: jax.Array) -> jax.Array:
    h = jnp.einsum("bsd,df->bsf", x, p["wi"].astype(x.dtype))
    if "bi" in p:
        h = h + p["bi"].astype(x.dtype)
    h = jax.nn.gelu(wlc(h, "batch", "seq", "act_mlp"))
    y = jnp.einsum("bsf,fd->bsd", h, p["wo"].astype(x.dtype))
    if "bo" in p:
        y = y + p["bo"].astype(x.dtype)
    return wlc(y, "batch", "seq", "act_embed")


def init_mlp(b: ParamBuilder, name: str, kind: str, d_model: int, d_ff: int):
    """kind: swiglu | geglu | gelu | relu2."""
    if kind in ("swiglu", "geglu"):
        init_swiglu(b, name, d_model, d_ff)
    else:
        init_gelu_mlp(b, name, d_model, d_ff, bias=(kind == "gelu"))


def mlp_apply(p: Dict, x: jax.Array, kind: str) -> jax.Array:
    if kind == "swiglu":
        return swiglu(p, x)
    if kind == "geglu":
        return geglu(p, x)
    if kind == "relu2":
        h = jnp.einsum("bsd,df->bsf", x, p["wi"].astype(x.dtype))
        h = jnp.square(jax.nn.relu(wlc(h, "batch", "seq", "act_mlp")))
        y = jnp.einsum("bsf,fd->bsd", h, p["wo"].astype(x.dtype))
        return wlc(y, "batch", "seq", "act_embed")
    return gelu_mlp(p, x)


def init_geglu(b: ParamBuilder, name: str, d_model: int, d_ff: int):
    init_swiglu(b, name, d_model, d_ff)


def geglu(p: Dict, x: jax.Array) -> jax.Array:
    g = jnp.einsum("bsd,df->bsf", x, p["wi_gate"].astype(x.dtype))
    u = jnp.einsum("bsd,df->bsf", x, p["wi_up"].astype(x.dtype))
    h = jax.nn.gelu(g) * u
    h = wlc(h, "batch", "seq", "act_mlp")
    y = jnp.einsum("bsf,fd->bsd", h, p["wo"].astype(x.dtype))
    return wlc(y, "batch", "seq", "act_embed")


# ---------------------------------------------------------------------------
# Embedding / logits
# ---------------------------------------------------------------------------


def init_embedding(b: ParamBuilder, name: str, vocab: int, d_model: int):
    s = b.scope(name)
    s.param("table", (vocab, d_model), ("vocab", "embed"), init=normal_init(1.0))


def init_unembedding(b: ParamBuilder, name: str, vocab: int, d_model: int):
    """Untied output head: 1/sqrt(d_model) init keeps the initial logits at
    unit scale, so a fresh model's loss starts at ln(vocab)."""
    s = b.scope(name)
    s.param("table", (vocab, d_model), ("vocab", "embed"), init=scaled_init(-1))


def embed(p: Dict, tokens: jax.Array, dtype: Any = jnp.float32) -> jax.Array:
    x = p["table"].astype(dtype)[tokens]
    return wlc(x, "batch", "seq", "act_embed")


def logits(p: Dict, x: jax.Array) -> jax.Array:
    y = jnp.einsum("bsd,vd->bsv", x, p["table"].astype(x.dtype))
    return wlc(y, "batch", "seq", "act_vocab")


def init_linear(
    b: ParamBuilder, name: str, d_in: int, d_out: int,
    axes: Tuple[Optional[str], Optional[str]] = ("embed", "mlp"),
    bias: bool = False,
):
    s = b.scope(name)
    s.param("w", (d_in, d_out), axes, init=scaled_init(0))
    if bias:
        s.param("b", (d_out,), (axes[1],), init=zeros_init())


def linear(p: Dict, x: jax.Array) -> jax.Array:
    y = x @ p["w"].astype(x.dtype)
    if "b" in p:
        y = y + p["b"].astype(x.dtype)
    return y
