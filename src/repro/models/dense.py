"""Dense llama-style decoder LM (GQA + RoPE + SwiGLU), config-driven.

Covers deepseek-coder-33b, minitron-8b, deepseek-7b, qwen1.5-4b, and serves as the
backbone for qwen2-vl (see :mod:`repro.models.vlm`).

Entry points:
  * ``init_params(cfg, key/abstract)``       -> (params, logical_axes)
  * ``forward(params, cfg, tokens)``         -> logits               (train)
  * ``prefill(params, cfg, tokens, cache_len)`` -> (logits, cache)   (inference)
  * ``decode_step(params, cfg, token, cache, pos)`` -> (logits, cache)
"""
from __future__ import annotations

from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.distributed.sharding import with_logical_constraint as wlc
from repro.models import layers as L
from repro.models.config import ModelConfig
from repro.models.param import ParamBuilder, build, stacked

PyTree = Any


# ---------------------------------------------------------------------------
# Params
# ---------------------------------------------------------------------------


def _init_block(s, cfg: ModelConfig):
    hd = cfg.resolved_head_dim()
    L.init_rmsnorm(s, "ln1", cfg.d_model)
    L.init_attention(
        s, "attn", cfg.d_model, cfg.n_heads, cfg.n_kv_heads, hd,
        qkv_bias=cfg.qkv_bias, qk_norm=cfg.qk_norm,
    )
    L.init_rmsnorm(s, "ln2", cfg.d_model)
    L.init_mlp(s, "mlp", cfg.mlp, cfg.d_model, cfg.d_ff)


def init_params(
    cfg: ModelConfig,
    key: Optional[jax.Array] = None,
    abstract: bool = False,
    dtype: Any = None,
) -> Tuple[PyTree, PyTree]:
    dtype = dtype or cfg.dtype

    def f(b: ParamBuilder):
        L.init_embedding(b, "embedding", cfg.vocab, cfg.d_model)
        _init_block(stacked(b, cfg.n_layers).scope("blocks"), cfg)
        L.init_rmsnorm(b, "ln_f", cfg.d_model)
        if not cfg.tie_embeddings:
            L.init_unembedding(b, "lm_head", cfg.vocab, cfg.d_model)

    return build(f, key=key, abstract=abstract, dtype=dtype)


# ---------------------------------------------------------------------------
# Blocks
# ---------------------------------------------------------------------------


def _block_train(lp: Dict, x: jax.Array, cfg: ModelConfig, positions: jax.Array,
                 mrope_positions=None) -> jax.Array:
    # the scopes name the ops (backward and rematerialized ones too) in the
    # compiled step, so a device trace attributes its time to a layer
    with jax.named_scope("attention"):
        h = L.rms_norm(lp["ln1"], x)
        h = L.attention_train(
            lp["attn"], h, positions=positions, causal=True, window=cfg.window,
            rope_theta=cfg.rope_theta,
            mrope_sections=cfg.mrope_sections or None,
            mrope_positions=mrope_positions,
            precision=cfg.train_precision,
        )
        x = x + h
    with jax.named_scope("mlp"):
        h = L.rms_norm(lp["ln2"], x)
        return x + L.mlp_apply(lp["mlp"], h, cfg.mlp)


def _scan_blocks(params: PyTree, x: jax.Array, cfg: ModelConfig, body) -> jax.Array:
    blocks = params["blocks"]
    fn = jax.checkpoint(body) if cfg.remat else body  # full remat per layer
    if cfg.scan_layers:
        def step(carry, lp):
            return fn(lp, carry), None

        with L.repeated_layers(cfg.n_layers):
            x, _ = jax.lax.scan(step, x, blocks)
    else:
        # unrolled: used by smoke tests and the dry-run's cost calibration
        # (XLA cost_analysis counts a scan body ONCE; unrolled HLO counts all)
        for i in range(cfg.n_layers):
            lp = jax.tree_util.tree_map(lambda a: a[i], blocks)
            x = fn(lp, x)
    return x


def _final(params: PyTree, x: jax.Array, cfg: ModelConfig) -> jax.Array:
    with jax.named_scope("vocab"):
        x = L.rms_norm(params["ln_f"], x)
        head = params["embedding"] if cfg.tie_embeddings else params["lm_head"]
        y = L.logits(head, x)
        if cfg.logit_softcap:
            y = jnp.tanh(y / cfg.logit_softcap) * cfg.logit_softcap
        return y


# ---------------------------------------------------------------------------
# Train / prefill / decode
# ---------------------------------------------------------------------------


def forward(
    params: PyTree,
    cfg: ModelConfig,
    tokens: jax.Array,
    *,
    inputs_embeds: Optional[jax.Array] = None,
    mrope_positions: Optional[jax.Array] = None,
) -> jax.Array:
    """Training forward. tokens: (B, S) int32 -> logits (B, S, V)."""
    if inputs_embeds is not None:
        x = inputs_embeds.astype(cfg.dtype)
    else:
        with jax.named_scope("vocab"):
            x = L.embed(params["embedding"], tokens, cfg.dtype)
    S = x.shape[1]
    positions = jnp.arange(S)
    body = partial(
        lambda lp, h: _block_train(lp, h, cfg, positions, mrope_positions)
    )
    x = _scan_blocks(params, x, cfg, lambda lp, h: body(lp, h))
    return _final(params, x, cfg)


def init_cache(cfg: ModelConfig, batch: int, cache_len: int, dtype=None) -> PyTree:
    dtype = dtype or cfg.dtype
    hd = cfg.resolved_head_dim()
    shape = (cfg.n_layers, batch, cache_len, cfg.n_kv_heads, hd)
    if cfg.kv_cache_dtype == "int8":
        # per-row symmetric int8 + f32 scale column: ~4x fewer KV-pool bytes
        sshape = shape[:-1] + (1,)
        return {
            "k": jnp.zeros(shape, jnp.int8),
            "k_scale": jnp.zeros(sshape, jnp.float32),
            "v": jnp.zeros(shape, jnp.int8),
            "v_scale": jnp.zeros(sshape, jnp.float32),
        }
    return {
        "k": jnp.zeros(shape, dtype),
        "v": jnp.zeros(shape, dtype),
    }


def cache_logical_axes(cfg: ModelConfig) -> PyTree:
    ax = ("layers", "batch", "kv_seq", "act_kv_heads", None)
    if cfg.kv_cache_dtype == "int8":
        return {"k": ax, "k_scale": ax, "v": ax, "v_scale": ax}
    return {"k": ax, "v": ax}


def prefill(
    params: PyTree,
    cfg: ModelConfig,
    tokens: jax.Array,
    cache_len: int,
    *,
    inputs_embeds: Optional[jax.Array] = None,
    mrope_positions: Optional[jax.Array] = None,
) -> Tuple[jax.Array, PyTree]:
    """Run the prompt, return last-position logits + KV cache."""
    if inputs_embeds is not None:
        x = inputs_embeds.astype(cfg.dtype)
    else:
        x = L.embed(params["embedding"], tokens, cfg.dtype)
    S = x.shape[1]
    positions = jnp.arange(S)

    def body(lp, h):
        hn = L.rms_norm(lp["ln1"], h)
        attn_out, kv = L.attention_prefill(
            lp["attn"], hn, positions=positions, cache_len=cache_len,
            causal=True, window=cfg.window, rope_theta=cfg.rope_theta,
            mrope_sections=(cfg.mrope_sections or None)
            if mrope_positions is not None else None,
            mrope_positions=mrope_positions,
            kv_cache_dtype=cfg.kv_cache_dtype,
        )
        h = h + attn_out
        hn = L.rms_norm(lp["ln2"], h)
        return h + L.mlp_apply(lp["mlp"], hn, cfg.mlp), kv

    if cfg.scan_layers:
        fn = jax.checkpoint(body) if cfg.remat else body

        def step(carry, lp):
            h, kv = fn(lp, carry)
            return h, kv

        x, cache = jax.lax.scan(step, x, params["blocks"])
    else:
        kvs = []
        for i in range(cfg.n_layers):
            lp = jax.tree_util.tree_map(lambda a: a[i], params["blocks"])
            x, kv = body(lp, x)
            kvs.append(kv)
        cache = jax.tree_util.tree_map(lambda *ls: jnp.stack(ls), *kvs)
    return _final(params, x[:, -1:], cfg), cache


def _maybe_unrolled_scan(cfg, body, x, blocks_and_state):
    """scan when cfg.scan_layers else an unrolled Python loop (same semantics)."""
    if cfg.scan_layers:
        return jax.lax.scan(body, x, blocks_and_state)
    n = jax.tree_util.tree_leaves(blocks_and_state)[0].shape[0]
    outs = []
    for i in range(n):
        xs = jax.tree_util.tree_map(lambda a: a[i], blocks_and_state)
        x, out = body(x, xs)
        outs.append(out)
    stacked = jax.tree_util.tree_map(
        lambda *ls: jnp.stack(ls), *outs
    ) if outs and outs[0] is not None else None
    return x, stacked


def decode_step(
    params: PyTree,
    cfg: ModelConfig,
    token: jax.Array,   # (B, 1) int32
    cache: PyTree,      # {"k","v"}: (L, B, Skv, Hkv, D)
    pos: jax.Array,     # (B,) absolute position of the new token
    rope_offset: Optional[jax.Array] = None,  # (B,): rope at pos+offset (VLM)
) -> Tuple[jax.Array, PyTree]:
    x = L.embed(params["embedding"], token, cfg.dtype)
    rope_pos = pos if rope_offset is None else pos + rope_offset

    def body(h, xs):
        lp, kv = xs
        hn = L.rms_norm(lp["ln1"], h)
        attn_out, kv = L.attention_decode(
            lp["attn"], hn, kv, pos=rope_pos, window=cfg.window,
            rope_theta=cfg.rope_theta, slot=pos,
        )
        h = h + attn_out
        hn = L.rms_norm(lp["ln2"], h)
        return h + L.mlp_apply(lp["mlp"], hn, cfg.mlp), kv

    x, new_cache = _maybe_unrolled_scan(cfg, body, x, (params["blocks"], cache))
    return _final(params, x, cfg), new_cache
