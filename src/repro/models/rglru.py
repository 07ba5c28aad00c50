"""RecurrentGemma / Griffin-style hybrid: RG-LRU recurrent blocks + local attention,
repeating (R, R, A) pattern.  Sub-quadratic => runs the long_500k shape.

RG-LRU recurrence (per channel, c = 8):
    r_t = sigmoid(x_t W_a + b_a)                      (recurrence gate)
    i_t = sigmoid(x_t W_x + b_x)                      (input gate)
    log a_t = -c * softplus(Lambda) * r_t
    h_t = exp(log a_t) * h_{t-1} + sqrt(1 - exp(2 log a_t)) * (i_t * x_t)

The temporal-mixing recurrent block is:  linear-in (2 branches) -> [causal conv1d(4)
-> RG-LRU] * gelu-gate -> linear-out.  Each layer is temporal-mix + GeGLU MLP, both
pre-norm residual.  Training uses an associative scan (or the Pallas blocked-scan
kernel); decode carries (conv window, lru state) per layer.
"""
from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.distributed.sharding import with_logical_constraint as wlc
from repro.models import layers as L
from repro.models.config import ModelConfig
from repro.models.param import (
    ParamBuilder, build, constant_init, normal_init, scaled_init, stacked,
    uniform_init, zeros_init,
)

PyTree = Any
C_RGLRU = 8.0


# ---------------------------------------------------------------------------
# RG-LRU core
# ---------------------------------------------------------------------------


def init_rglru(b, name: str, width: int):
    s = b.scope(name)
    s.param("wa", (width,), ("lru",), init=zeros_init())       # diagonal gates
    s.param("ba", (width,), ("lru",), init=zeros_init())
    s.param("wx", (width,), ("lru",), init=zeros_init())
    s.param("bx", (width,), ("lru",), init=zeros_init())
    # Lambda init so that a = sigmoid(Lambda) in [0.9, 0.999] (paper init)
    s.param("lam", (width,), ("lru",), init=uniform_init(2.2, 6.9))


def _rglru_gates(p: Dict, x: jax.Array):
    """x: (B, S, W) -> (log_a, gated_x) both (B, S, W), float32."""
    xf = x.astype(jnp.float32)
    r = jax.nn.sigmoid(xf * p["wa"].astype(jnp.float32) + p["ba"].astype(jnp.float32))
    i = jax.nn.sigmoid(xf * p["wx"].astype(jnp.float32) + p["bx"].astype(jnp.float32))
    log_a = -C_RGLRU * jax.nn.softplus(p["lam"].astype(jnp.float32)) * r
    gated = jnp.sqrt(jnp.maximum(1.0 - jnp.exp(2.0 * log_a), 1e-12)) * (i * xf)
    return log_a, gated


def rglru_scan(p: Dict, x: jax.Array, h0: Optional[jax.Array] = None,
               precision: str = "f32") -> jax.Array:
    """Associative-scan reference. x: (B, S, W) -> y: (B, S, W)."""
    log_a, gated = _rglru_gates(p, x)
    a = jnp.exp(log_a)
    if h0 is not None:
        gated = gated.at[:, 0].add(a[:, 0] * h0.astype(jnp.float32))

    def combine(c1, c2):
        a1, b1 = c1
        a2, b2 = c2
        return a1 * a2, a2 * b1 + b2

    from repro.models.layers import FLAGS

    if precision == "int8-fused":
        from repro.kernels import ops as kops

        # gated input streams as int8 + per-row scales; the decay a stays f32
        # (seq padding inside the kernel must be exactly 1.0 to pass the carry)
        y = kops.rglru_scan_q8(
            a, gated, interpret=kops.interpret_default(),
            use_kernel=FLAGS.use_pallas,
        )
    elif FLAGS.use_pallas:
        if precision == "bf16":
            gated = gated.astype(jnp.bfloat16).astype(jnp.float32)
        from repro.kernels import ops as kops

        y = kops.rglru_scan(a, gated, interpret=kops.interpret_default())
    else:
        if precision == "bf16":
            gated = gated.astype(jnp.bfloat16).astype(jnp.float32)
        _, y = jax.lax.associative_scan(combine, (a, gated), axis=1)
    return y.astype(x.dtype)


def rglru_step(p: Dict, x: jax.Array, h: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """One decode step. x: (B, 1, W), h: (B, W) -> (y, new_h)."""
    log_a, gated = _rglru_gates(p, x)
    a = jnp.exp(log_a[:, 0])
    new_h = a * h.astype(jnp.float32) + gated[:, 0]
    return new_h[:, None].astype(x.dtype), new_h


# ---------------------------------------------------------------------------
# Causal depthwise conv1d (width 4)
# ---------------------------------------------------------------------------


def init_conv1d(b, name: str, width: int, ksize: int):
    s = b.scope(name)
    s.param("w", (ksize, width), ("conv", "lru"), init=normal_init(0.02))
    s.param("b", (width,), ("lru",), init=zeros_init())


def causal_conv1d(p: Dict, x: jax.Array) -> jax.Array:
    """Depthwise causal conv. x: (B, S, W)."""
    k = p["w"].shape[0]
    xp = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    out = sum(
        xp[:, i : i + x.shape[1]] * p["w"][i].astype(x.dtype) for i in range(k)
    )
    return out + p["b"].astype(x.dtype)


def conv1d_step(p: Dict, x: jax.Array, window: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Decode step. x: (B, 1, W); window: (B, k-1, W) past inputs."""
    k = p["w"].shape[0]
    full = jnp.concatenate([window, x], axis=1)          # (B, k, W)
    out = jnp.einsum("bkw,kw->bw", full.astype(jnp.float32),
                     p["w"].astype(jnp.float32))[:, None]
    out = out.astype(x.dtype) + p["b"].astype(x.dtype)
    return out, full[:, 1:]


# ---------------------------------------------------------------------------
# Blocks
# ---------------------------------------------------------------------------


def init_recurrent_block(s, cfg: ModelConfig):
    w = cfg.lru_width or cfg.d_model
    L.init_linear(s, "in_rec", cfg.d_model, w, axes=("embed", "lru"))
    L.init_linear(s, "in_gate", cfg.d_model, w, axes=("embed", "lru"))
    init_conv1d(s, "conv", w, cfg.conv_width)
    init_rglru(s, "lru", w)
    L.init_linear(s, "out", w, cfg.d_model, axes=("lru", "embed"))


def recurrent_block(
    lp: Dict, x: jax.Array, cfg: ModelConfig, return_state: bool = False
):
    rec_in = L.linear(lp["in_rec"], x)
    gate = jax.nn.gelu(L.linear(lp["in_gate"], x))
    rec = causal_conv1d(lp["conv"], rec_in)
    rec = rglru_scan(lp["lru"], rec, precision=cfg.train_precision)
    y = rec * gate
    y = wlc(y, "batch", "seq", "act_mlp")
    out = L.linear(lp["out"], y)
    if not return_state:
        return out
    # decode-ready state: conv window = last (k-1) conv INPUTS (zero-padded on
    # the left when the prompt is shorter); lru h = last scan output.
    k = lp["conv"]["w"].shape[0]
    S = rec_in.shape[1]
    win = rec_in[:, max(0, S - (k - 1)):]
    if S < k - 1:
        win = jnp.pad(win, ((0, 0), (k - 1 - S, 0), (0, 0)))
    state = {"conv": win, "lru": rec[:, -1].astype(jnp.float32)}
    return out, state


def recurrent_block_step(
    lp: Dict, x: jax.Array, state: Dict
) -> Tuple[jax.Array, Dict]:
    rec = L.linear(lp["in_rec"], x)
    gate = jax.nn.gelu(L.linear(lp["in_gate"], x))
    rec, conv_win = conv1d_step(lp["conv"], rec, state["conv"])
    rec, h = rglru_step(lp["lru"], rec, state["lru"])
    y = rec * gate
    return L.linear(lp["out"], y), {"conv": conv_win, "lru": h}


def _init_layer(s, cfg: ModelConfig, kind: str):
    L.init_rmsnorm(s, "ln1", cfg.d_model)
    if kind == "A":
        hd = cfg.resolved_head_dim()
        L.init_attention(s, "attn", cfg.d_model, cfg.n_heads, cfg.n_kv_heads, hd)
    else:
        init_recurrent_block(s, cfg)
    L.init_rmsnorm(s, "ln2", cfg.d_model)
    L.init_geglu(s, "mlp", cfg.d_model, cfg.d_ff)


def layer_kinds(cfg: ModelConfig):
    pat = cfg.block_pattern or ("R", "R", "A")
    return [pat[i % len(pat)] for i in range(cfg.n_layers)]


def init_params(cfg: ModelConfig, key=None, abstract=False, dtype=None):
    """Layers are grouped per *kind* into separate stacked scan groups.

    ``groups`` in the param tree: {"R": stacked recurrent layers, "A": stacked
    attention layers}; execution interleaves them by the pattern.
    """
    dtype = dtype or cfg.dtype
    kinds = layer_kinds(cfg)
    n_r = sum(1 for k in kinds if k == "R")
    n_a = len(kinds) - n_r

    def f(b: ParamBuilder):
        L.init_embedding(b, "embedding", cfg.vocab, cfg.d_model)
        g = b.scope("groups")
        if n_r:
            _init_layer(stacked(g, n_r).scope("R"), cfg, "R")
        if n_a:
            _init_layer(stacked(g, n_a).scope("A"), cfg, "A")
        L.init_rmsnorm(b, "ln_f", cfg.d_model)
        if not cfg.tie_embeddings:
            L.init_unembedding(b, "lm_head", cfg.vocab, cfg.d_model)

    return build(f, key=key, abstract=abstract, dtype=dtype)


def _layer_train(lp: Dict, x: jax.Array, cfg: ModelConfig, kind: str,
                 positions: jax.Array) -> jax.Array:
    h = L.rms_norm(lp["ln1"], x)
    if kind == "A":
        h = L.attention_train(
            lp["attn"], h, positions=positions, causal=True,
            window=cfg.window, rope_theta=cfg.rope_theta,
            precision=cfg.train_precision,
        )
    else:
        h = recurrent_block(lp, h, cfg)
    x = x + h
    h = L.rms_norm(lp["ln2"], x)
    return x + L.geglu(lp["mlp"], h)


def forward(params, cfg: ModelConfig, tokens, **_) -> jax.Array:
    x = L.embed(params["embedding"], tokens, cfg.dtype)
    positions = jnp.arange(x.shape[1])
    kinds = layer_kinds(cfg)

    # Interleave two scan groups by the pattern: run each group's layers in
    # pattern order.  Scans stay uniform per group; the interleave is a Python
    # loop over *pattern cycles* with dynamic slices into the stacked groups.
    # For HLO compactness we scan each contiguous same-kind run.
    idx = {"R": 0, "A": 0}
    i = 0
    while i < len(kinds):
        j = i
        while j < len(kinds) and kinds[j] == kinds[i]:
            j += 1
        kind, n_run = kinds[i], j - i
        group = params["groups"][kind]
        run = jax.tree_util.tree_map(
            lambda a: jax.lax.slice_in_dim(a, idx[kind], idx[kind] + n_run), group
        )

        def body(h, lp, _kind=kind):
            out = _layer_train(lp, h, cfg, _kind, positions)
            return out, None

        fn = jax.checkpoint(lambda lp, h, _k=kind: _layer_train(lp, h, cfg, _k, positions)) \
            if cfg.remat else (lambda lp, h, _k=kind: _layer_train(lp, h, cfg, _k, positions))
        if cfg.scan_layers:
            with L.repeated_layers(n_run):
                x, _ = jax.lax.scan(lambda c, lp: (fn(lp, c), None), x, run)
        else:
            for li in range(n_run):
                lp = jax.tree_util.tree_map(lambda a: a[li], run)
                x = fn(lp, x)
        idx[kind] += n_run
        i = j

    from repro.models.dense import _final

    return _final(params, x, cfg)


def prefill(params, cfg: ModelConfig, tokens, cache_len: int, **_):
    """Run the prompt; return (last-position logits, decode-ready cache).

    A-layer caches are rotating windows of ``min(window, cache_len)`` rows
    holding the last in-window KVs (absolute RoPE phases); R-layer states are
    (conv window, final lru h).
    """
    x = L.embed(params["embedding"], tokens, cfg.dtype)
    S = tokens.shape[1]
    positions = jnp.arange(S)
    kinds = layer_kinds(cfg)
    attn_len = min(cache_len, cfg.window or cache_len)

    A_kv, R_conv, R_lru = [], [], []
    idx = {"R": 0, "A": 0}
    for kind in kinds:
        lp = jax.tree_util.tree_map(lambda a: a[idx[kind]], params["groups"][kind])
        h = L.rms_norm(lp["ln1"], x)
        if kind == "A":
            h, kv = L.attention_prefill(
                lp["attn"], h, positions=positions, cache_len=attn_len,
                causal=True, window=cfg.window, rope_theta=cfg.rope_theta,
                rotating=True, kv_cache_dtype=cfg.kv_cache_dtype,
            )
            A_kv.append(kv)
        else:
            h, st = recurrent_block(lp, h, cfg, return_state=True)
            R_conv.append(st["conv"])
            R_lru.append(st["lru"])
        x = x + h
        h = L.rms_norm(lp["ln2"], x)
        x = x + L.geglu(lp["mlp"], h)
        idx[kind] += 1

    empty_a = (
        {"k": jnp.zeros((0,)), "k_scale": jnp.zeros((0,)),
         "v": jnp.zeros((0,)), "v_scale": jnp.zeros((0,))}
        if cfg.kv_cache_dtype == "int8"
        else {"k": jnp.zeros((0,)), "v": jnp.zeros((0,))}
    )
    cache = {
        "A": jax.tree_util.tree_map(lambda *ls: jnp.stack(ls), *A_kv)
        if A_kv else empty_a,
        "R": {"conv": jnp.stack(R_conv), "lru": jnp.stack(R_lru)} if R_conv else {
            "conv": jnp.zeros((0,)), "lru": jnp.zeros((0,))},
    }
    from repro.models.dense import _final

    return _final(params, x[:, -1:], cfg), cache


# ---------------------------------------------------------------------------
# Decode: state = attention KV caches (A layers) + (conv, lru) states (R layers)
# ---------------------------------------------------------------------------


def init_cache(cfg: ModelConfig, batch: int, cache_len: int, dtype=None):
    dtype = dtype or cfg.dtype
    kinds = layer_kinds(cfg)
    n_r = sum(1 for k in kinds if k == "R")
    n_a = len(kinds) - n_r
    hd = cfg.resolved_head_dim()
    w = cfg.lru_width or cfg.d_model
    attn_len = min(cache_len, cfg.window or cache_len)
    kv_shape = (n_a, batch, attn_len, cfg.n_kv_heads, hd)
    if cfg.kv_cache_dtype == "int8":
        a_cache = {
            "k": jnp.zeros(kv_shape, jnp.int8),
            "k_scale": jnp.zeros(kv_shape[:-1] + (1,), jnp.float32),
            "v": jnp.zeros(kv_shape, jnp.int8),
            "v_scale": jnp.zeros(kv_shape[:-1] + (1,), jnp.float32),
        }
    else:
        a_cache = {
            "k": jnp.zeros(kv_shape, dtype),
            "v": jnp.zeros(kv_shape, dtype),
        }
    return {
        "A": a_cache,
        "R": {
            "conv": jnp.zeros((n_r, batch, cfg.conv_width - 1, w), dtype),
            "lru": jnp.zeros((n_r, batch, w), jnp.float32),
        },
    }


def cache_logical_axes(cfg: ModelConfig):
    kv = ("layers", "batch", "kv_seq", "act_kv_heads", None)
    a_axes = (
        {"k": kv, "k_scale": kv, "v": kv, "v_scale": kv}
        if cfg.kv_cache_dtype == "int8" else {"k": kv, "v": kv}
    )
    return {
        "A": a_axes,
        "R": {
            "conv": ("layers", "batch", None, "lru"),
            "lru": ("layers", "batch", "lru"),
        },
    }


def decode_step(params, cfg: ModelConfig, token, cache, pos):
    """Local-attention KV cache is a rotating window of size cfg.window.

    Keys keep their ABSOLUTE RoPE phase; the roll evicts the oldest key, so
    every cached key is in-window by construction (no window mask needed) and
    attention distances stay exact.
    """
    x = L.embed(params["embedding"], token, cfg.dtype)
    kinds = layer_kinds(cfg)
    window = cfg.window or cache.get("A", {}).get("k", jnp.zeros((1, 1, 1))).shape[2]

    new_A, new_conv, new_lru = [], [], []
    idx = {"R": 0, "A": 0}
    for i, kind in enumerate(kinds):
        lp = jax.tree_util.tree_map(
            lambda a: a[idx[kind]], params["groups"][kind]
        )
        h = L.rms_norm(lp["ln1"], x)
        if kind == "A":
            # every KV leaf (2-leaf native or 4-leaf int8 + scales) rides
            # the same rotating-window roll: scale columns are (B, S, H, 1)
            kv = {n: c[idx["A"]] for n, c in cache["A"].items()}
            cache_rows = kv["k"].shape[1]
            win = min(window, cache_rows)
            # rotating-window slot; if full, roll left then write the last row
            slot = jnp.minimum(pos, win - 1)
            def roll_if_full(c):
                rolled = jnp.roll(c, -1, axis=1)
                return jnp.where((pos >= win)[:, None, None, None], rolled, c)

            kv = {k: roll_if_full(v) for k, v in kv.items()}
            attn_out, kv = L.attention_decode(
                lp["attn"], h, kv,
                pos=pos, rope_theta=cfg.rope_theta,
                slot=slot, valid_len=jnp.minimum(pos + 1, win),
            )
            new_A.append(kv)
            h = attn_out
        else:
            st = {
                "conv": cache["R"]["conv"][idx["R"]],
                "lru": cache["R"]["lru"][idx["R"]],
            }
            h, st = recurrent_block_step(lp, h, st)
            new_conv.append(st["conv"])
            new_lru.append(st["lru"])
        x = x + h
        h = L.rms_norm(lp["ln2"], x)
        x = x + L.geglu(lp["mlp"], h)
        idx[kind] += 1

    new_cache = {
        "A": jax.tree_util.tree_map(lambda *ls: jnp.stack(ls), *new_A)
        if new_A else cache["A"],
        "R": {"conv": jnp.stack(new_conv), "lru": jnp.stack(new_lru)}
        if new_conv else cache["R"],
    }
    from repro.models.dense import _final

    return _final(params, x, cfg), new_cache
