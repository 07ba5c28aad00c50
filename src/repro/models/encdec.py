"""Whisper-style encoder-decoder transformer (whisper-medium backbone).

The audio conv frontend is a STUB per the assignment: ``input_specs()`` feeds
precomputed frame embeddings (B, n_frames, d_model).  Encoder uses sinusoidal
positions and bidirectional attention; decoder uses learned positions, causal
self-attention + cross-attention.  LayerNorm + GELU MLP with biases (Whisper
convention), pre-norm.
"""
from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models import layers as L
from repro.models.config import ModelConfig
from repro.models.param import ParamBuilder, build, normal_init, stacked

PyTree = Any


def sinusoids(length: int, channels: int) -> jax.Array:
    log_timescale = math.log(10000.0) / (channels // 2 - 1)
    inv = jnp.exp(-log_timescale * jnp.arange(channels // 2, dtype=jnp.float32))
    t = jnp.arange(length, dtype=jnp.float32)[:, None] * inv[None, :]
    return jnp.concatenate([jnp.sin(t), jnp.cos(t)], axis=1)


def _init_enc_layer(s, cfg: ModelConfig):
    hd = cfg.resolved_head_dim()
    L.init_layernorm(s, "ln1", cfg.d_model)
    L.init_attention(s, "attn", cfg.d_model, cfg.n_heads, cfg.n_kv_heads, hd,
                     qkv_bias=True)
    L.init_layernorm(s, "ln2", cfg.d_model)
    L.init_gelu_mlp(s, "mlp", cfg.d_model, cfg.d_ff, bias=True)


def _init_dec_layer(s, cfg: ModelConfig):
    hd = cfg.resolved_head_dim()
    L.init_layernorm(s, "ln1", cfg.d_model)
    L.init_attention(s, "self_attn", cfg.d_model, cfg.n_heads, cfg.n_kv_heads, hd,
                     qkv_bias=True)
    L.init_layernorm(s, "ln_x", cfg.d_model)
    L.init_attention(s, "cross_attn", cfg.d_model, cfg.n_heads, cfg.n_kv_heads, hd,
                     qkv_bias=True)
    L.init_layernorm(s, "ln2", cfg.d_model)
    L.init_gelu_mlp(s, "mlp", cfg.d_model, cfg.d_ff, bias=True)


def init_params(cfg: ModelConfig, key=None, abstract=False, dtype=None,
                max_dec_len: int = 448):
    dtype = dtype or cfg.dtype

    def f(b: ParamBuilder):
        L.init_embedding(b, "embedding", cfg.vocab, cfg.d_model)
        b.param("dec_pos", (max_dec_len, cfg.d_model), ("pos", "embed"),
                init=normal_init(0.01))
        _init_enc_layer(stacked(b, cfg.n_enc_layers).scope("enc_blocks"), cfg)
        L.init_layernorm(b, "ln_enc", cfg.d_model)
        _init_dec_layer(stacked(b, cfg.n_dec_layers).scope("dec_blocks"), cfg)
        L.init_layernorm(b, "ln_dec", cfg.d_model)

    return build(f, key=key, abstract=abstract, dtype=dtype)


# ---------------------------------------------------------------------------
# Encoder
# ---------------------------------------------------------------------------


def encode(params, cfg: ModelConfig, frames: jax.Array) -> jax.Array:
    """frames: (B, n_frames, d_model) stubbed conv-frontend output."""
    x = frames.astype(cfg.dtype) + sinusoids(
        frames.shape[1], cfg.d_model
    ).astype(cfg.dtype)
    positions = jnp.arange(x.shape[1])

    def body(lp, h):
        a = L.attention_train(
            lp["attn"], L.layer_norm(lp["ln1"], h),
            positions=positions, causal=False, use_rope=False,
            precision=cfg.train_precision,
        )
        h = h + a
        return h + L.gelu_mlp(lp["mlp"], L.layer_norm(lp["ln2"], h))

    fn = jax.checkpoint(body) if cfg.remat else body
    if cfg.scan_layers:
        with L.repeated_layers(cfg.n_enc_layers):
            x, _ = jax.lax.scan(lambda c, lp: (fn(lp, c), None), x, params["enc_blocks"])
    else:
        for i in range(cfg.n_enc_layers):
            lp = jax.tree_util.tree_map(lambda a: a[i], params["enc_blocks"])
            x = fn(lp, x)
    return L.layer_norm(params["ln_enc"], x)


# ---------------------------------------------------------------------------
# Decoder (train)
# ---------------------------------------------------------------------------


def _dec_positions(params, S: int, offset=0):
    table = params["dec_pos"]
    maxlen = table.shape[0]
    idx = jnp.minimum(jnp.arange(S) + offset, maxlen - 1)
    return table[idx]


def decode_train(params, cfg: ModelConfig, tokens: jax.Array,
                 enc_out: jax.Array) -> jax.Array:
    x = L.embed(params["embedding"], tokens, cfg.dtype)
    x = x + _dec_positions(params, tokens.shape[1]).astype(cfg.dtype)
    positions = jnp.arange(tokens.shape[1])

    def body(lp, h):
        a = L.attention_train(
            lp["self_attn"], L.layer_norm(lp["ln1"], h),
            positions=positions, causal=True, use_rope=False,
            precision=cfg.train_precision,
        )
        h = h + a
        ck, cv = L.cross_kv(lp["cross_attn"], enc_out)
        h = h + L.cross_attention(lp["cross_attn"], L.layer_norm(lp["ln_x"], h), ck, cv)
        return h + L.gelu_mlp(lp["mlp"], L.layer_norm(lp["ln2"], h))

    fn = jax.checkpoint(body) if cfg.remat else body
    if cfg.scan_layers:
        with L.repeated_layers(cfg.n_dec_layers):
            x, _ = jax.lax.scan(lambda c, lp: (fn(lp, c), None), x, params["dec_blocks"])
    else:
        for i in range(cfg.n_dec_layers):
            lp = jax.tree_util.tree_map(lambda a: a[i], params["dec_blocks"])
            x = fn(lp, x)
    x = L.layer_norm(params["ln_dec"], x)
    return L.logits(params["embedding"], x)


def forward(params, cfg: ModelConfig, tokens, *, frames=None, **_) -> jax.Array:
    """Full enc-dec training forward."""
    enc_out = encode(params, cfg, frames)
    return decode_train(params, cfg, tokens, enc_out)


# ---------------------------------------------------------------------------
# Decoder (serve): self-KV cache + precomputed cross-KV
# ---------------------------------------------------------------------------


def init_cache(cfg: ModelConfig, batch: int, cache_len: int, dtype=None):
    dtype = dtype or cfg.dtype
    hd = cfg.resolved_head_dim()
    Ln = cfg.n_dec_layers
    kv_shape = (Ln, batch, cache_len, cfg.n_kv_heads, hd)
    x_shape = (Ln, batch, cfg.n_frames, cfg.n_kv_heads, hd)
    if cfg.kv_cache_dtype == "int8":
        # self-attn KV and the (large, static) cross-attn KV both store
        # per-row symmetric int8 + f32 scale columns
        return {
            "k": jnp.zeros(kv_shape, jnp.int8),
            "k_scale": jnp.zeros(kv_shape[:-1] + (1,), jnp.float32),
            "v": jnp.zeros(kv_shape, jnp.int8),
            "v_scale": jnp.zeros(kv_shape[:-1] + (1,), jnp.float32),
            "xk": jnp.zeros(x_shape, jnp.int8),
            "xk_scale": jnp.zeros(x_shape[:-1] + (1,), jnp.float32),
            "xv": jnp.zeros(x_shape, jnp.int8),
            "xv_scale": jnp.zeros(x_shape[:-1] + (1,), jnp.float32),
        }
    return {
        "k": jnp.zeros(kv_shape, dtype),
        "v": jnp.zeros(kv_shape, dtype),
        "xk": jnp.zeros(x_shape, dtype),
        "xv": jnp.zeros(x_shape, dtype),
    }


def cache_logical_axes(cfg: ModelConfig):
    kv = ("layers", "batch", "kv_seq", "act_kv_heads", None)
    x = ("layers", "batch", None, "act_kv_heads", None)
    if cfg.kv_cache_dtype == "int8":
        return {
            "k": kv, "k_scale": kv, "v": kv, "v_scale": kv,
            "xk": x, "xk_scale": x, "xv": x, "xv_scale": x,
        }
    return {"k": kv, "v": kv, "xk": x, "xv": x}


def build_cross_cache(params, cfg: ModelConfig, enc_out: jax.Array):
    def per_layer(lp):
        return L.cross_kv(lp["cross_attn"], enc_out)

    xk, xv = jax.lax.map(per_layer, params["dec_blocks"])
    return xk, xv


def prefill(params, cfg: ModelConfig, tokens, cache_len: int, *,
            frames=None, **_):
    """Encode audio frames, run the decoder prompt, return decode-ready cache:
    self-attn KV (padded to cache_len) + per-layer cross-attn KV."""
    enc_out = encode(params, cfg, frames)
    x = L.embed(params["embedding"], tokens, cfg.dtype)
    x = x + _dec_positions(params, tokens.shape[1]).astype(cfg.dtype)
    positions = jnp.arange(tokens.shape[1])

    kvs, crosses = [], []
    n = cfg.n_dec_layers
    for i in range(n):
        lp = jax.tree_util.tree_map(lambda a: a[i], params["dec_blocks"])
        a, kv = L.attention_prefill(
            lp["self_attn"], L.layer_norm(lp["ln1"], x),
            positions=positions, cache_len=cache_len, causal=True,
            use_rope=False, kv_cache_dtype=cfg.kv_cache_dtype,
        )
        x = x + a
        ck, cv = L.cross_kv(lp["cross_attn"], enc_out)
        x = x + L.cross_attention(
            lp["cross_attn"], L.layer_norm(lp["ln_x"], x), ck, cv
        )
        x = x + L.gelu_mlp(lp["mlp"], L.layer_norm(lp["ln2"], x))
        kvs.append(kv)
        if cfg.kv_cache_dtype == "int8":
            from repro.kernels import ref as KR

            xkq, xks = KR.quantize_int8_ref(ck)
            xvq, xvs = KR.quantize_int8_ref(cv)
            crosses.append({
                "xk": xkq, "xk_scale": xks, "xv": xvq, "xv_scale": xvs,
            })
        else:
            crosses.append({"xk": ck, "xv": cv})
    x = L.layer_norm(params["ln_dec"], x)
    cache = jax.tree_util.tree_map(lambda *ls: jnp.stack(ls), *[
        {**kv, **cross} for kv, cross in zip(kvs, crosses)
    ])
    return L.logits(params["embedding"], x[:, -1:]), cache


def decode_step(params, cfg: ModelConfig, token, cache, pos):
    x = L.embed(params["embedding"], token, cfg.dtype)
    pos_emb = params["dec_pos"][
        jnp.minimum(pos, params["dec_pos"].shape[0] - 1)
    ]  # (B, d)
    x = x + pos_emb[:, None].astype(cfg.dtype)

    def body(h, xs):
        lp, kv = xs
        self_kv = {n: kv[n] for n in kv if not n.startswith("x")}
        a, new_kv = L.attention_decode(
            lp["self_attn"], L.layer_norm(lp["ln1"], h),
            self_kv, pos=pos, use_rope=False,
        )
        h = h + a
        if "xk_scale" in kv:
            from repro.kernels import ref as KR

            ck = KR.dequantize_int8_ref(kv["xk"], kv["xk_scale"], cfg.dtype)
            cv = KR.dequantize_int8_ref(kv["xv"], kv["xv_scale"], cfg.dtype)
        else:
            ck, cv = kv["xk"], kv["xv"]
        h = h + L.cross_attention(
            lp["cross_attn"], L.layer_norm(lp["ln_x"], h), ck, cv
        )
        h = h + L.gelu_mlp(lp["mlp"], L.layer_norm(lp["ln2"], h))
        cross = {n: kv[n] for n in kv if n.startswith("x")}
        return h, {**new_kv, **cross}

    from repro.models.dense import _maybe_unrolled_scan

    x, new_cache = _maybe_unrolled_scan(cfg, body, x, (params["dec_blocks"], cache))
    x = L.layer_norm(params["ln_dec"], x)
    return L.logits(params["embedding"], x), new_cache
