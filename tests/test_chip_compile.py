"""Main-path Pallas kernels compiled for a described TPU v5e at real widths.

Nothing runs: each case lowers and compiles one kernel with ``interpret=False``
for one chip of a ``v5e:2x2`` topology that is described, not attached.  The
TPU compiler then refuses what interpret mode cannot see — block shapes that
do not align with the tiling, and more fast memory (VMEM) than a kernel may
use.  Widths:

  * attention at deepseek-7b's 32 heads x 128, seq 2048, bf16; the
    training pair (splash forward, and its dq/dkv backward under
    ``jax.grad``) also at deepseek-coder-33b's 56 query heads over 8 kv heads;
  * fused MoE at qwen3-moe-30b-a3b's d_model 2048, expert d_ff 768, top-8,
    with 32 experts held (128 over 4 chips) and 2048 tokens;
  * rwkv6 at rwkv6-7b's 64 heads x 64;
  * the whole training step of qwen3-moe-30b-a3b's one-chip share, with its
    grouped matmul, at 8 rows x 4096 (it has to fit the chip's 16 GiB).

The topology is described inside a fixture (never at import or collection),
so each pytest-xdist worker collects the same tests and only the worker that
runs this file loads the TPU library.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import fused_moe as FM
from repro.kernels import ops
from repro.models.moe import expert_capacity

SEQ, HEADS, HEAD_DIM = 2048, 32, 128
GQA_HEADS, GQA_KV_HEADS = 56, 8
DECODE_BATCH, PAGE = 8, 16
MOE_TOKENS, MOE_D, MOE_FF, MOE_EXPERTS, MOE_K = 2048, 2048, 768, 32, 8
MOE_CAPACITY = expert_capacity(MOE_TOKENS, MOE_EXPERTS, MOE_K, 1.25)
RWKV_HEADS, RWKV_DIM = 64, 64

bf16, f32, i32, i8 = jnp.bfloat16, jnp.float32, jnp.int32, jnp.int8
ATT = (1, SEQ, HEADS, HEAD_DIM)
GQA_Q = (1, SEQ, GQA_HEADS, HEAD_DIM)
GQA_KV = (1, SEQ, GQA_KV_HEADS, HEAD_DIM)
KV = (DECODE_BATCH, SEQ, HEADS, HEAD_DIM)
POOL = (DECODE_BATCH * SEQ // PAGE, PAGE, HEADS, HEAD_DIM)
TABLE = (DECODE_BATCH, SEQ // PAGE)
Q1 = (DECODE_BATCH, 1, HEADS, HEAD_DIM)
WKV = (1, SEQ, RWKV_HEADS, RWKV_DIM)
SLOTS = MOE_EXPERTS * MOE_CAPACITY

def _splash(q, k, v):
    return ops.splash_causal_attention(q, k, v, interpret=False)


def _splash_grad(q, k, v):
    return jax.grad(
        lambda q, k, v: jnp.sum(_splash(q, k, v).astype(f32)),
        argnums=(0, 1, 2))(q, k, v)


# name -> (kernel call, argument (shape, dtype) list)
CASES = {
    "splash_attention": (_splash, [(ATT, bf16)] * 3),
    "splash_attention_grad": (_splash_grad, [(ATT, bf16)] * 3),
    "splash_attention_gqa": (_splash, [(GQA_Q, bf16)] + [(GQA_KV, bf16)] * 2),
    "splash_attention_gqa_grad": (
        _splash_grad, [(GQA_Q, bf16)] + [(GQA_KV, bf16)] * 2),
    "flash_attention": (
        lambda q, k, v: ops.flash_attention(q, k, v, causal=True,
                                            interpret=False),
        [(ATT, bf16)] * 3,
    ),
    "flash_attention_q8": (
        lambda q, k, v: ops.flash_attention_q8(q, k, v, causal=True,
                                               interpret=False),
        [(ATT, bf16)] * 3,
    ),
    "decode_attention": (
        lambda q, k, v, n: ops.decode_attention(q, k, v, n, interpret=False),
        [(Q1, bf16), (KV, bf16), (KV, bf16), ((DECODE_BATCH,), i32)],
    ),
    "decode_attention_int8": (
        lambda q, k, ks, v, vs, n: ops.decode_attention_int8(
            q, k, ks, v, vs, n, interpret=False),
        [(Q1, bf16), (KV, i8), (KV[:3] + (1,), f32), (KV, i8),
         (KV[:3] + (1,), f32), ((DECODE_BATCH,), i32)],
    ),
    "paged_decode_attention": (
        lambda q, k, v, t, n: ops.paged_decode_attention(
            q, k, v, t, n, interpret=False),
        [(Q1, bf16), (POOL, bf16), (POOL, bf16), (TABLE, i32),
         ((DECODE_BATCH,), i32)],
    ),
    "paged_decode_attention_int8": (
        lambda q, k, ks, v, vs, t, n: ops.paged_decode_attention_int8(
            q, k, ks, v, vs, t, n, interpret=False),
        [(Q1, bf16), (POOL, i8), (POOL[:3] + (1,), f32), (POOL, i8),
         (POOL[:3] + (1,), f32), (TABLE, i32), ((DECODE_BATCH,), i32)],
    ),
    "fused_moe_mlp": (
        lambda x, r, wg, wu, wo: ops.fused_moe_mlp(
            x, r, wg, wu, wo, k=MOE_K, capacity=MOE_CAPACITY,
            interpret=False),
        [((MOE_TOKENS, MOE_D), bf16), ((MOE_D, MOE_EXPERTS), bf16),
         ((MOE_EXPERTS, MOE_D, MOE_FF), bf16),
         ((MOE_EXPERTS, MOE_D, MOE_FF), bf16),
         ((MOE_EXPERTS, MOE_FF, MOE_D), bf16)],
    ),
    "fused_moe_combine": (
        lambda y, tok: FM.fused_moe_combine(
            y, tok, MOE_TOKENS, capacity=MOE_CAPACITY, interpret=False),
        [((SLOTS, MOE_D), bf16), ((SLOTS, 1), i32)],
    ),
    "rwkv6_scan": (
        lambda r, k, v, w, u: ops.rwkv6_scan(r, k, v, w, u, interpret=False),
        [(WKV, bf16)] * 4 + [((RWKV_HEADS, RWKV_DIM), f32)],
    ),
    "rwkv6_scan_q8": (
        lambda r, k, v, w, u: ops.rwkv6_scan_q8(r, k, v, w, u,
                                                interpret=False),
        [(WKV, bf16)] * 4 + [((RWKV_HEADS, RWKV_DIM), f32)],
    ),
    "quantize_int8": (
        lambda x, noise: ops.quantize_int8(x, noise, interpret=False),
        [((4096, 4096), f32)] * 2,
    ),
}


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip cannot be read back from the cache."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_compiles_for_v5e(name, one_chip, no_persistent_cache):
    fn, specs = CASES[name]
    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
            for shape, dtype in specs]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_qwen3_moe_share_step_compiles_for_v5e(one_chip, no_persistent_cache,
                                               monkeypatch):
    """The whole training step of one chip's share of qwen3-moe-30b-a3b
    (4 layers, 16 of 128 experts, an eighth of the vocabulary) at 8 rows x
    4096, on the TPU's paths (splash attention, the megablox grouped
    matmul, the expert layer's choice of buffer), fits one v5e's 16 GiB."""
    from repro.configs.qwen3_moe_30b_a3b import CONFIG
    from repro.models.api import get_model
    from repro.optim import adamw
    from repro.train.steps import make_train_step

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(ops, "interpret_default", lambda: False)
    model = get_model(CONFIG.with_(n_layers=4, vocab=18992, experts_held=16))
    opt = adamw(b1=0.9, b2=0.95, eps=1e-8, weight_decay=0.1)
    step = make_train_step(model, opt, lambda s: jnp.float32(1e-3))
    params, _ = model.init_params(abstract=True)
    on_chip = lambda t: jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip), t)
    rows = {k: jax.ShapeDtypeStruct((8, 4096), dt, sharding=one_chip)
            for k, dt in (("tokens", i32), ("labels", i32), ("loss_mask", f32))}
    compiled = jax.jit(step, donate_argnums=(0, 1)).lower(
        on_chip(params), on_chip(jax.eval_shape(opt.init, params)), rows
    ).compile()
    text = compiled.as_text()
    assert "splash_mha_fwd" in text and "gmm" in text and "tgmm" in text
    # the expert layer picks its compact or its static buffer on the device
    assert " conditional(" in text
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16 * 2**30
