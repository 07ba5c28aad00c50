"""RWKV-6 "Finch": attention-free LM with data-dependent decay (arXiv:2404.05892).

Per-layer time-mixing with matrix-valued state S in R^{H x D x D} (H heads, D=64):

    w_t = exp(-exp(w0 + tanh(x_t A_w) B_w))            (data-dependent decay)
    out_t = r_t . (S_{t-1} + (u k_t^T) v_t)            (bonus term u for current tok)
    S_t   = diag(w_t) S_{t-1} + k_t^T v_t

Token-shift mixing (lerp of x_t and x_{t-1}) for r/k/v/g/w; output head-wise
GroupNorm and SiLU(g) gating.  Channel-mixing is the squared-ReLU MLP.  O(1)-state
decode => runs long_500k.  Training uses a chunked scan (Pallas kernel) or a
lax.scan reference.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.distributed.sharding import with_logical_constraint as wlc
from repro.models import layers as L
from repro.models.config import ModelConfig
from repro.models.param import (
    ParamBuilder, build, normal_init, ones_init, scaled_init, stacked, zeros_init,
)

PyTree = Any


# ---------------------------------------------------------------------------
# WKV6 recurrence
# ---------------------------------------------------------------------------


def wkv6_ref(
    r: jax.Array, k: jax.Array, v: jax.Array, w: jax.Array, u: jax.Array,
    s0: Optional[jax.Array] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Reference scan.  r/k/v/w: (B, S, H, D); u: (H, D).

    Returns out: (B, S, H, D) and final state (B, H, D, D).
    State recurrence: S_t = diag(w_t) S_{t-1} + k_t outer v_t;
    out_t = r_t @ (S_{t-1} + diag(u) k_t outer v_t).
    """
    B, S, H, D = r.shape
    s = jnp.zeros((B, H, D, D), jnp.float32) if s0 is None else s0.astype(jnp.float32)

    def step(s, xs):
        rt, kt, vt, wt = xs  # (B, H, D)
        kv = kt[..., :, None] * vt[..., None, :]          # (B, H, D, D)
        out = jnp.einsum("bhd,bhde->bhe", rt, s + u[..., :, None] * kv)
        s = wt[..., :, None] * s + kv
        return s, out

    xs = tuple(
        jnp.moveaxis(t.astype(jnp.float32), 1, 0) for t in (r, k, v, w)
    )  # (S, B, H, D)
    s, outs = jax.lax.scan(step, s, xs)
    return jnp.moveaxis(outs, 0, 1).astype(r.dtype), s


def wkv6_step(
    r: jax.Array, k: jax.Array, v: jax.Array, w: jax.Array, u: jax.Array,
    s: jax.Array,
) -> Tuple[jax.Array, jax.Array]:
    """Single decode step. r/k/v/w: (B, H, D); s: (B, H, D, D)."""
    rf, kf, vf, wf = (t.astype(jnp.float32) for t in (r, k, v, w))
    sf = s.astype(jnp.float32)
    kv = kf[..., :, None] * vf[..., None, :]
    out = jnp.einsum("bhd,bhde->bhe", rf, sf + u[..., :, None] * kv)
    s_new = wf[..., :, None] * sf + kv
    return out.astype(r.dtype), s_new


# ---------------------------------------------------------------------------
# Time mixing
# ---------------------------------------------------------------------------


def init_time_mix(b, cfg: ModelConfig):
    d = cfg.d_model
    la = cfg.decay_lora
    s = b.scope("tmix")
    for nm in ("mu_r", "mu_k", "mu_v", "mu_g", "mu_w"):
        s.param(nm, (d,), ("lru",), init=normal_init(0.02))
    s.param("wr", (d, d), ("embed", "lru"), init=scaled_init(0))
    s.param("wk", (d, d), ("embed", "lru"), init=scaled_init(0))
    s.param("wv", (d, d), ("embed", "lru"), init=scaled_init(0))
    s.param("wg", (d, d), ("embed", "lru"), init=scaled_init(0))
    s.param("wo", (d, d), ("lru", "embed"), init=scaled_init(0))
    # data-dependent decay LoRA
    s.param("w0", (d,), ("lru",), init=normal_init(0.5))
    s.param("wa", (d, la), ("embed", None), init=scaled_init(0))
    s.param("wb", (la, d), (None, "lru"), init=zeros_init())
    # per-head bonus
    s.param("u", (d,), ("lru",), init=normal_init(0.5))
    # head-wise group norm
    s.param("gn_scale", (d,), ("lru",), init=ones_init())
    s.param("gn_bias", (d,), ("lru",), init=zeros_init())


def _token_shift(x: jax.Array, x_prev: Optional[jax.Array] = None) -> jax.Array:
    """Returns x_{t-1}; for the first token uses x_prev (decode) or zeros."""
    if x_prev is None:
        return jnp.pad(x, ((0, 0), (1, 0), (0, 0)))[:, :-1]
    return jnp.concatenate([x_prev[:, None], x[:, :-1]], axis=1)


def _heads(x: jax.Array, hd: int) -> jax.Array:
    B, S, d = x.shape
    return x.reshape(B, S, d // hd, hd)


def _group_norm(p: Dict, x: jax.Array, hd: int, eps: float = 64e-5) -> jax.Array:
    """Head-wise group norm over (..., H, D) flattened back to channels."""
    B, S, H, D = x.shape
    xf = x.astype(jnp.float32)
    mu = xf.mean(axis=-1, keepdims=True)
    var = ((xf - mu) ** 2).mean(axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    y = y.reshape(B, S, H * D)
    return (y * p["gn_scale"].astype(jnp.float32)
            + p["gn_bias"].astype(jnp.float32)).astype(x.dtype)


def time_mix(
    p: Dict, x: jax.Array, cfg: ModelConfig,
    state: Optional[Dict] = None,
    return_state: bool = False,
) -> Tuple[jax.Array, Optional[Dict]]:
    """x: (B, S, d).  state (decode): {"shift": (B, d), "wkv": (B, H, D, D)}.

    ``return_state=True`` on the full-sequence path returns the decode-ready
    state after the last position (prefill)."""
    hd = cfg.rwkv_head_dim
    H = cfg.d_model // hd
    xp = _token_shift(x, state["shift"] if state else None)

    def mix(mu):
        return x + (xp - x) * jax.nn.sigmoid(mu.astype(x.dtype))

    r = mix(p["mu_r"]) @ p["wr"].astype(x.dtype)
    k = mix(p["mu_k"]) @ p["wk"].astype(x.dtype)
    v = mix(p["mu_v"]) @ p["wv"].astype(x.dtype)
    g = mix(p["mu_g"]) @ p["wg"].astype(x.dtype)
    xw = mix(p["mu_w"])
    decay_in = jnp.tanh(xw @ p["wa"].astype(x.dtype)) @ p["wb"].astype(x.dtype)
    w = jnp.exp(
        -jnp.exp(
            jnp.clip(p["w0"].astype(jnp.float32) + decay_in.astype(jnp.float32),
                     -10.0, 5.0)
        )
    )                                                   # (B, S, d) in (0,1)

    r4, k4, v4, w4 = (_heads(t, hd) for t in (r, k, v, w.astype(x.dtype)))
    u = p["u"].astype(jnp.float32).reshape(H, hd)

    from repro.models.layers import FLAGS

    if state is None:
        precision = cfg.train_precision
        if precision == "bf16":
            r4, k4, v4 = (t.astype(jnp.bfloat16) for t in (r4, k4, v4))
        if precision == "int8-fused":
            from repro.kernels import ops as kops

            # r/k/v stream through the kernel as int8 + per-row scales; the
            # decay w stays f32 (its log-cumsum is the overflow-safety math)
            out, _s = kops.rwkv6_scan_q8(
                r4, k4, v4, w4, u,
                interpret=kops.interpret_default(), use_kernel=FLAGS.use_pallas,
            )
        elif FLAGS.use_pallas:
            from repro.kernels import ops as kops

            out, _s = kops.rwkv6_scan(
                r4, k4, v4, w4, u, interpret=kops.interpret_default()
            )
        else:
            out, _s = wkv6_ref(r4, k4, v4, w4, u)
        new_state = {"shift": x[:, -1], "wkv": _s} if return_state else None
    else:
        out, s_new = wkv6_step(
            r4[:, 0], k4[:, 0], v4[:, 0], w4[:, 0], u, state["wkv"]
        )
        out = out[:, None]
        new_state = {"shift": x[:, -1], "wkv": s_new}

    out = _group_norm(p, out, hd)
    out = out * jax.nn.silu(g)
    out = wlc(out, "batch", "seq", "act_mlp")
    return out @ p["wo"].astype(x.dtype), new_state


# ---------------------------------------------------------------------------
# Channel mixing
# ---------------------------------------------------------------------------


def init_channel_mix(b, cfg: ModelConfig):
    s = b.scope("cmix")
    s.param("mu_r", (cfg.d_model,), ("lru",), init=normal_init(0.02))
    s.param("mu_k", (cfg.d_model,), ("lru",), init=normal_init(0.02))
    s.param("wr", (cfg.d_model, cfg.d_model), ("embed", "lru"), init=scaled_init(0))
    s.param("wk", (cfg.d_model, cfg.d_ff), ("embed", "mlp"), init=scaled_init(0))
    s.param("wv", (cfg.d_ff, cfg.d_model), ("mlp", "embed"), init=scaled_init(0))


def channel_mix(
    p: Dict, x: jax.Array, state: Optional[Dict] = None
) -> Tuple[jax.Array, Optional[Dict]]:
    xp = _token_shift(x, state["shift"] if state else None)

    def mix(mu):
        return x + (xp - x) * jax.nn.sigmoid(mu.astype(x.dtype))

    r = jax.nn.sigmoid(mix(p["mu_r"]) @ p["wr"].astype(x.dtype))
    k = mix(p["mu_k"]) @ p["wk"].astype(x.dtype)
    k = jnp.square(jax.nn.relu(k))
    k = wlc(k, "batch", "seq", "act_mlp")
    out = r * (k @ p["wv"].astype(x.dtype))
    new_state = {"shift": x[:, -1]} if state is not None else None
    return out, new_state


# ---------------------------------------------------------------------------
# Model
# ---------------------------------------------------------------------------


def _init_block(s, cfg: ModelConfig):
    L.init_layernorm(s, "ln1", cfg.d_model)
    init_time_mix(s, cfg)
    L.init_layernorm(s, "ln2", cfg.d_model)
    init_channel_mix(s, cfg)


def init_params(cfg: ModelConfig, key=None, abstract=False, dtype=None):
    dtype = dtype or cfg.dtype

    def f(b: ParamBuilder):
        L.init_embedding(b, "embedding", cfg.vocab, cfg.d_model)
        L.init_layernorm(b, "ln0", cfg.d_model)
        _init_block(stacked(b, cfg.n_layers).scope("blocks"), cfg)
        L.init_layernorm(b, "ln_f", cfg.d_model)
        if not cfg.tie_embeddings:
            L.init_unembedding(b, "lm_head", cfg.vocab, cfg.d_model)

    return build(f, key=key, abstract=abstract, dtype=dtype)


def _block_train(lp, x, cfg: ModelConfig):
    h, _ = time_mix(lp["tmix"], L.layer_norm(lp["ln1"], x), cfg)
    x = x + h
    h, _ = channel_mix(lp["cmix"], L.layer_norm(lp["ln2"], x))
    return x + h


def forward(params, cfg: ModelConfig, tokens, **_) -> jax.Array:
    x = L.embed(params["embedding"], tokens, cfg.dtype)
    x = L.layer_norm(params["ln0"], x)

    def body(lp, h):
        return _block_train(lp, h, cfg)

    fn = jax.checkpoint(body) if cfg.remat else body
    if cfg.scan_layers:
        x, _ = jax.lax.scan(lambda c, lp: (fn(lp, c), None), x, params["blocks"])
    else:
        for i in range(cfg.n_layers):
            lp = jax.tree_util.tree_map(lambda a: a[i], params["blocks"])
            x = fn(lp, x)
    x = L.layer_norm(params["ln_f"], x)
    head = params["embedding"] if cfg.tie_embeddings else params["lm_head"]
    return L.logits(head, x)


def prefill(params, cfg: ModelConfig, tokens, cache_len: int = 0, **_):
    """Run the prompt; return (last-position logits, O(1) recurrent state)."""
    x = L.embed(params["embedding"], tokens, cfg.dtype)
    x = L.layer_norm(params["ln0"], x)

    tshift, cshift, wkv = [], [], []
    for i in range(cfg.n_layers):
        lp = jax.tree_util.tree_map(lambda a: a[i], params["blocks"])
        xn = L.layer_norm(lp["ln1"], x)
        t_out, st = time_mix(lp["tmix"], xn, cfg, return_state=True)
        tshift.append(st["shift"])
        wkv.append(st["wkv"])
        x = x + t_out
        xn = L.layer_norm(lp["ln2"], x)
        cshift.append(xn[:, -1])
        c_out, _ = channel_mix(lp["cmix"], xn)
        x = x + c_out
    x = L.layer_norm(params["ln_f"], x)
    head = params["embedding"] if cfg.tie_embeddings else params["lm_head"]
    cache = {
        "tshift": jnp.stack(tshift),
        "cshift": jnp.stack(cshift),
        "wkv": jnp.stack(wkv),
    }
    return L.logits(head, x[:, -1:]), cache


def init_cache(cfg: ModelConfig, batch: int, cache_len: int = 0, dtype=None):
    """RWKV state is O(1) in sequence length (cache_len unused)."""
    dtype = dtype or cfg.dtype
    hd = cfg.rwkv_head_dim
    H = cfg.d_model // hd
    Ln = cfg.n_layers
    return {
        "tshift": jnp.zeros((Ln, batch, cfg.d_model), dtype),
        "cshift": jnp.zeros((Ln, batch, cfg.d_model), dtype),
        "wkv": jnp.zeros((Ln, batch, H, hd, hd), jnp.float32),
    }


def cache_logical_axes(cfg: ModelConfig):
    return {
        "tshift": ("layers", "batch", "lru"),
        "cshift": ("layers", "batch", "lru"),
        "wkv": ("layers", "batch", "lru", None, None),
    }


def decode_step(params, cfg: ModelConfig, token, cache, pos):
    x = L.embed(params["embedding"], token, cfg.dtype)
    x = L.layer_norm(params["ln0"], x)

    def body(h, xs):
        lp, st = xs
        t_out, t_state = time_mix(
            lp["tmix"], L.layer_norm(lp["ln1"], h), cfg,
            state={"shift": st["tshift"], "wkv": st["wkv"]},
        )
        h = h + t_out
        c_out, c_state = channel_mix(
            lp["cmix"], L.layer_norm(lp["ln2"], h), state={"shift": st["cshift"]}
        )
        h = h + c_out
        return h, {
            "tshift": t_state["shift"],
            "cshift": c_state["shift"],
            "wkv": t_state["wkv"],
        }

    from repro.models.dense import _maybe_unrolled_scan

    x, new_cache = _maybe_unrolled_scan(cfg, body, x, (params["blocks"], cache))
    x = L.layer_norm(params["ln_f"], x)
    head = params["embedding"] if cfg.tie_embeddings else params["lm_head"]
    return L.logits(head, x), new_cache
