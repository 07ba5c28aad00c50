"""qwen3-moe-30b-a3b [moe]: 48L, d_model=2048, 32H x 128 (GQA kv=4), RMSNorm on
each query and key head before RoPE (theta 1e6), vocab=151936 (untied), every
layer sparse: 128 SwiGLU experts of width 768, top-8 with softmax routing and
the 8 gates renormalised, no shared expert, dropless, router aux weight 0.001
[hf:Qwen/Qwen3-30B-A3B, arXiv:2505.09388].

The aux loss is the Switch form E·Σ_e (count_e/T)·p_e per layer, summed over
layers; HF's ``load_balancing_loss_func`` pools all layers' router outputs
before taking the product.
"""
import jax.numpy as jnp

from repro.models.config import ModelConfig

CONFIG = ModelConfig(
    name="qwen3-moe-30b-a3b",
    family="moe",
    n_layers=48,
    d_model=2048,
    n_heads=32,
    n_kv_heads=4,
    head_dim=128,
    qk_norm=True,
    d_ff=768,               # per-expert hidden
    vocab=151936,
    n_experts=128,
    experts_per_token=8,
    capacity_factor=None,   # dropless
    router_aux_weight=0.001,
    mlp="swiglu",
    rope_theta=1000000.0,
    fsdp=True,
    dtype=jnp.bfloat16,
)

SMOKE = CONFIG.with_(
    n_layers=2, d_model=64, n_heads=4, n_kv_heads=2, head_dim=16,
    d_ff=64, vocab=256, n_experts=8, experts_per_token=2,
    fsdp=False, dtype=jnp.float32,
)
