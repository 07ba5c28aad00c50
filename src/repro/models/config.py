"""Shared model configuration dataclass for every assigned architecture."""
from __future__ import annotations

import dataclasses
from typing import Any, Optional, Tuple

import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    # identity
    name: str = "model"
    family: str = "dense"            # dense | moe | rglru | rwkv6 | encdec | vlm
    modality: str = "text"           # text | audio | vision

    # transformer dims
    n_layers: int = 2
    d_model: int = 256
    n_heads: int = 4
    n_kv_heads: int = 4
    d_ff: int = 1024
    vocab: int = 1024
    head_dim: Optional[int] = None   # default: d_model // n_heads
    qkv_bias: bool = False
    # RMSNorm over head_dim on each query and key head before RoPE (Qwen3);
    # its two scales exist only when on
    qk_norm: bool = False
    rope_theta: float = 10000.0
    norm: str = "rmsnorm"            # rmsnorm | layernorm
    mlp: str = "swiglu"              # swiglu | gelu | geglu
    tie_embeddings: bool = False

    # MoE.  ``n_experts`` is the router's width; this chip holds
    # ``experts_held`` of them (None: all), from ``first_expert`` on.
    # ``capacity_factor`` None is dropless routing (every token copy is
    # computed); a factor keeps the capacity-buffer paths, which drop overflow
    n_experts: int = 0
    experts_per_token: int = 0
    experts_held: Optional[int] = None
    first_expert: int = 0
    capacity_factor: Optional[float] = 1.25
    # weight of the summed per-layer Switch load-balancing loss in the
    # training objective
    router_aux_weight: float = 0.01
    # fused Pallas dispatch+expert-GEMM kernel for the single-program path
    # (the group-local EP path takes precedence under a >1 "model" mesh)
    fused_moe: bool = True

    # hybrid / recurrent (RecurrentGemma)
    block_pattern: Tuple[str, ...] = ()   # cycle of "R" (recurrent) / "A" (attention)
    window: Optional[int] = None          # local attention window
    lru_width: Optional[int] = None
    conv_width: int = 4

    # rwkv
    rwkv_head_dim: int = 64
    decay_lora: int = 64

    # enc-dec (Whisper)
    n_enc_layers: int = 0
    n_dec_layers: int = 0
    n_frames: int = 1500              # encoder positions (stubbed conv frontend)

    # vlm (Qwen2-VL)
    mrope_sections: Tuple[int, ...] = ()
    n_vision_patches: int = 0         # stubbed patch-embedding prefix length

    # numerics / structure
    dtype: Any = jnp.float32
    # "native" keeps the decode KV cache in `dtype`; "int8" stores per-row
    # symmetric int8 + f32 scales and dequantizes inside the decode kernel
    kv_cache_dtype: str = "native"
    # training hot-loop precision:
    #   "f32"        — kernels stream activations at the model dtype
    #   "bf16"       — attention/scan operands cast to bf16 before the kernel
    #   "int8-fused" — K/V and scan activations quantized per-row to int8,
    #                  dequantized inside the Pallas sweep (f32 accumulation),
    #                  and saved-for-backward residuals kept as int8 + scales
    train_precision: str = "f32"
    remat: bool = True
    scan_layers: bool = True
    fsdp: bool = False                # ZeRO-3-style extra sharding over "data"
    logit_softcap: Optional[float] = None

    def resolved_head_dim(self) -> int:
        return self.head_dim if self.head_dim is not None else self.d_model // self.n_heads

    def resolved_experts_held(self) -> int:
        return self.n_experts if self.experts_held is None else self.experts_held

    def with_(self, **kw) -> "ModelConfig":
        return dataclasses.replace(self, **kw)

    # -- analytic parameter counts --------------------------------------------
    def param_count(self, active_only: bool = False) -> int:
        d, ff, V = self.d_model, self.d_ff, self.vocab
        hd = self.resolved_head_dim()
        attn = d * hd * (self.n_heads + 2 * self.n_kv_heads) + self.n_heads * hd * d
        if self.qk_norm:
            attn += 2 * hd

        if self.family == "moe":
            e = (self.experts_per_token if active_only
                 else self.resolved_experts_held())
            mlp_p = 3 * d * ff * e + d * self.n_experts  # experts + router
            per_layer = attn + mlp_p
            n = self.n_layers * per_layer
        elif self.family == "rglru":
            lw = self.lru_width or d
            rec = 2 * d * lw + lw * d + self.conv_width * lw + 3 * lw  # in/out + conv + gates
            mlp_p = 3 * d * ff
            n_att = sum(1 for i in range(self.n_layers)
                        if self.block_pattern[i % len(self.block_pattern)] == "A")
            n_rec = self.n_layers - n_att
            n = n_att * (attn + mlp_p) + n_rec * (rec + mlp_p)
        elif self.family == "rwkv6":
            heads = d // self.rwkv_head_dim
            tm = 6 * d * d + 2 * self.decay_lora * d + heads * self.rwkv_head_dim
            cm = 2 * d * ff
            n = self.n_layers * (tm + cm)
        elif self.family == "encdec":
            enc = self.n_enc_layers * (attn + 2 * d * ff)
            dec = self.n_dec_layers * (2 * attn + 2 * d * ff)
            n = enc + dec
        else:  # dense / vlm
            mlp_p = 3 * d * ff if self.mlp in ("swiglu", "geglu") else 2 * d * ff
            n = self.n_layers * (attn + mlp_p)
        n += V * d  # embedding
        if not self.tie_embeddings and self.family != "encdec":
            n += V * d  # untied lm head
        return int(n)
