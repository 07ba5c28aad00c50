"""Per-kernel shape/dtype sweeps vs the pure-jnp oracles (interpret=True)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _hypothesis_compat import given, settings, st

from repro.kernels import ops
from repro.kernels import ref as R

KEY = jax.random.PRNGKey(7)


def _rand(key, shape, dtype):
    x = jax.random.normal(key, shape, jnp.float32)
    return x.astype(dtype)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

FLASH_CASES = [
    # B, Sq, Skv, H, Hkv, D, causal, window, dtype
    (2, 128, 128, 4, 4, 64, False, None, jnp.float32),
    (2, 128, 128, 4, 2, 64, True, None, jnp.float32),
    (1, 256, 256, 8, 1, 64, True, 64, jnp.float32),
    (2, 100, 100, 4, 4, 32, True, None, jnp.float32),
    (1, 64, 64, 2, 2, 128, True, None, jnp.bfloat16),
    (1, 64, 64, 2, 1, 16, False, 16, jnp.float32),
]


@pytest.mark.parametrize("case", FLASH_CASES, ids=[str(c[:8]) for c in FLASH_CASES])
def test_flash_attention_matches_oracle(case):
    B, Sq, Skv, H, Hkv, D, causal, window, dtype = case
    ks = jax.random.split(KEY, 3)
    q = _rand(ks[0], (B, Sq, H, D), dtype)
    k = _rand(ks[1], (B, Skv, Hkv, D), dtype)
    v = _rand(ks[2], (B, Skv, Hkv, D), dtype)
    out = ops.flash_attention(q, k, v, causal=causal, window=window,
                              block=64, interpret=True)
    ref = R.flash_attention_ref(q, k, v, causal=causal, window=window)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(
        out.astype(jnp.float32), ref.astype(jnp.float32), atol=tol, rtol=tol
    )


def test_flash_attention_grad_matches_oracle():
    ks = jax.random.split(KEY, 3)
    q = _rand(ks[0], (1, 64, 2, 32), jnp.float32)
    k = _rand(ks[1], (1, 64, 2, 32), jnp.float32)
    v = _rand(ks[2], (1, 64, 2, 32), jnp.float32)
    g1 = jax.grad(lambda q: ops.flash_attention(
        q, k, v, causal=True, interpret=True).sum())(q)
    g2 = jax.grad(lambda q: R.flash_attention_ref(q, k, v, causal=True).sum())(q)
    np.testing.assert_allclose(g1, g2, atol=1e-5, rtol=1e-5)


# ---------------------------------------------------------------------------
# causal training attention: the splash forward + fused dq/dkv backward
# ---------------------------------------------------------------------------


def _rel(a, b):
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


# name -> (rows, seq, query heads, kv heads); the splash tile is the largest
# of 1024/512/256/128 dividing seq: 128 at 384, 256 at 256, 512 at 512, and
# 1024 (scores 512 keys at a time) at 1024
SPLASH_CASES = {
    "mha-s384-b128": (2, 384, 4, 4),
    "gqa7-s384-b128": (1, 384, 7, 1),
    "gqa7-s256-b256": (1, 256, 14, 2),
    "mha-s512-b512": (1, 512, 2, 2),
    "gqa7-s1024-b1024": (1, 1024, 7, 1),
}


@pytest.mark.parametrize("case", sorted(SPLASH_CASES))
def test_splash_causal_attention_matches_oracle(case):
    """bf16 operands through the kernel pair against the f32 oracle: the
    forward output and the q/k/v gradients agree to 2% in norm (bf16 rounds
    at 2^-9; these cases read 0.2-0.4%), and the causal mask holds."""
    B, S, H, Hkv = SPLASH_CASES[case]
    ks = jax.random.split(KEY, 4)
    q = _rand(ks[0], (B, S, H, 128), jnp.bfloat16)
    k = _rand(ks[1], (B, S, Hkv, 128), jnp.bfloat16)
    v = _rand(ks[2], (B, S, Hkv, 128), jnp.bfloat16)
    w = jax.random.normal(ks[3], (B, S, H, 128), jnp.float32)
    splash = jax.jit(lambda q, k, v: ops.splash_causal_attention(
        q, k, v, interpret=True))

    def grads(fn, *args):
        return jax.jit(jax.grad(
            lambda q, k, v: jnp.sum(fn(q, k, v).astype(jnp.float32) * w),
            argnums=(0, 1, 2)))(*args)

    out = splash(q, k, v)
    assert out.shape == q.shape and out.dtype == jnp.bfloat16
    f32 = [t.astype(jnp.float32) for t in (q, k, v)]
    assert _rel(out, R.splash_causal_attention_ref(*f32)) < 2e-2
    got = grads(splash, q, k, v)
    want = grads(R.splash_causal_attention_ref, *f32)
    for g, r, t in zip(got, want, (q, k, v)):
        assert g.shape == t.shape and g.dtype == t.dtype
        assert _rel(g, r) < 2e-2
    # causal: the later half of the values never reaches the first half
    v2 = v.at[:, S // 2:].set(0)
    np.testing.assert_array_equal(splash(q, k, v2)[:, : S // 2],
                                  out[:, : S // 2])


# ---------------------------------------------------------------------------
# decode attention
# ---------------------------------------------------------------------------

DECODE_CASES = [
    (2, 256, 4, 4, 64, None, jnp.float32),
    (3, 300, 8, 2, 64, 128, jnp.float32),
    (1, 64, 4, 1, 32, None, jnp.float32),
    (2, 128, 2, 2, 128, None, jnp.bfloat16),
]


@pytest.mark.parametrize("case", DECODE_CASES, ids=[str(c[:6]) for c in DECODE_CASES])
def test_decode_attention_matches_oracle(case):
    B, Skv, H, Hkv, D, window, dtype = case
    ks = jax.random.split(KEY, 4)
    q = _rand(ks[0], (B, 1, H, D), dtype)
    k = _rand(ks[1], (B, Skv, Hkv, D), dtype)
    v = _rand(ks[2], (B, Skv, Hkv, D), dtype)
    valid = jax.random.randint(ks[3], (B,), 1, Skv + 1)
    out = ops.decode_attention(q, k, v, valid, window=window,
                               block_k=128, interpret=True)
    ref = R.decode_attention_ref(q, k, v, valid, window=window)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(
        out.astype(jnp.float32), ref.astype(jnp.float32), atol=tol, rtol=tol
    )


# ---------------------------------------------------------------------------
# paged decode attention (block-table over a shared page pool)
# ---------------------------------------------------------------------------

PAGED_CASES = [
    # B, pool_pages, page_size, pages_per_row, H, Hkv, D, window, dtype
    (2, 12, 16, 4, 4, 4, 64, None, jnp.float32),
    (3, 16, 8, 5, 8, 2, 64, None, jnp.float32),
    (2, 10, 16, 3, 4, 1, 32, 24, jnp.float32),
    (2, 8, 8, 4, 2, 2, 128, None, jnp.bfloat16),
]


@pytest.mark.parametrize("case", PAGED_CASES, ids=[str(c[:8]) for c in PAGED_CASES])
def test_paged_decode_attention_matches_oracle(case):
    B, P, bs, NP, H, Hkv, D, window, dtype = case
    ks = jax.random.split(KEY, 5)
    q = _rand(ks[0], (B, 1, H, D), dtype)
    k_pages = _rand(ks[1], (P, bs, Hkv, D), dtype)
    v_pages = _rand(ks[2], (P, bs, Hkv, D), dtype)
    tbl = jax.random.randint(ks[3], (B, NP), 0, P, jnp.int32)
    valid = jax.random.randint(ks[4], (B,), 1, NP * bs + 1)
    out = ops.paged_decode_attention(q, k_pages, v_pages, tbl, valid,
                                     window=window, interpret=True)
    ref = R.paged_decode_attention_ref(q, k_pages, v_pages, tbl, valid,
                                       window=window)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(
        out.astype(jnp.float32), ref.astype(jnp.float32), atol=tol, rtol=tol
    )


def test_paged_decode_shared_prefix_pages_match_dense():
    """Rows sharing pool pages (a cached prefix) == dense attention on the
    per-row gathered cache — the paged path reads shared pages in place."""
    B, P, bs, NP, H, Hkv, D = 3, 8, 8, 4, 4, 2, 32
    ks = jax.random.split(KEY, 3)
    q = _rand(ks[0], (B, 1, H, D), jnp.float32)
    k_pages = _rand(ks[1], (P, bs, Hkv, D), jnp.float32)
    v_pages = _rand(ks[2], (P, bs, Hkv, D), jnp.float32)
    # all rows share prefix pages [0, 1]; suffixes diverge
    tbl = jnp.asarray([[0, 1, 2, 3], [0, 1, 4, 5], [0, 1, 6, 7]], jnp.int32)
    valid = jnp.asarray([NP * bs, 25, 17], jnp.int32)
    out = ops.paged_decode_attention(q, k_pages, v_pages, tbl, valid,
                                     interpret=True)
    k = k_pages[tbl].reshape(B, NP * bs, Hkv, D)
    v = v_pages[tbl].reshape(B, NP * bs, Hkv, D)
    dense = R.decode_attention_ref(q, k, v, valid)
    np.testing.assert_allclose(out, dense, atol=2e-5, rtol=2e-5)


# ---------------------------------------------------------------------------
# int8 decode attention (quantized KV cache, in-kernel dequantize)
# ---------------------------------------------------------------------------


def _quantized_kv(key, B, Skv, Hkv, D):
    k = jax.random.normal(key, (B, Skv, Hkv, D))
    v = jax.random.normal(jax.random.fold_in(key, 1), (B, Skv, Hkv, D))
    kq, ks = R.quantize_int8_ref(k)
    vq, vs = R.quantize_int8_ref(v)
    return kq, ks, vq, vs


INT8_DECODE_CASES = [
    # B, H, Hkv, D, Skv, window, block_k
    (2, 4, 4, 64, 128, None, 64),      # MHA
    (2, 8, 2, 64, 128, None, 64),      # GQA
    (1, 4, 1, 32, 100, None, 32),      # MQA, ragged Skv
    (2, 4, 2, 32, 96, 16, 32),         # GQA + window
    (1, 2, 2, 16, 40, 8, 512),         # window, single oversized block
]


@pytest.mark.parametrize(
    "case", INT8_DECODE_CASES, ids=[str(c) for c in INT8_DECODE_CASES]
)
def test_decode_attention_int8_matches_oracle(case):
    B, H, Hkv, D, Skv, window, block_k = case
    q = _rand(KEY, (B, 1, H, D), jnp.float32)
    kq, ks, vq, vs = _quantized_kv(jax.random.fold_in(KEY, 9), B, Skv, Hkv, D)
    valid = (jnp.arange(B, dtype=jnp.int32) * 13 % Skv) + 3
    out = ops.decode_attention_int8(
        q, kq, ks, vq, vs, valid, window=window, block_k=block_k, interpret=True
    )
    ref = R.decode_attention_int8_ref(q, kq, ks, vq, vs, valid, window=window)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


def test_decode_attention_int8_matches_f32_decode_closely():
    """Quantization error stays small: int8 path ≈ f32 path on the same KV."""
    B, H, Hkv, D, Skv = 2, 4, 2, 64, 64
    q = _rand(KEY, (B, 1, H, D), jnp.float32)
    k = jax.random.normal(jax.random.fold_in(KEY, 2), (B, Skv, Hkv, D))
    v = jax.random.normal(jax.random.fold_in(KEY, 3), (B, Skv, Hkv, D))
    kq, ks = R.quantize_int8_ref(k)
    vq, vs = R.quantize_int8_ref(v)
    valid = jnp.asarray([33, 64], jnp.int32)
    got = ops.decode_attention_int8(q, kq, ks, vq, vs, valid, interpret=True)
    want = R.decode_attention_ref(q, k, v, valid)
    np.testing.assert_allclose(got, want, atol=5e-2, rtol=5e-2)


INT8_PAGED_CASES = [
    # B, H, Hkv, D, n_pool, page, NP, window
    (3, 4, 4, 64, 16, 8, 4, None),     # MHA
    (2, 8, 2, 32, 12, 8, 5, None),     # GQA
    (2, 4, 2, 32, 10, 16, 3, 12),      # GQA + window
    (1, 2, 1, 16, 6, 8, 4, None),      # MQA
]


@pytest.mark.parametrize(
    "case", INT8_PAGED_CASES, ids=[str(c) for c in INT8_PAGED_CASES]
)
def test_paged_decode_attention_int8_matches_oracle(case):
    B, H, Hkv, D, n_pool, page, NP, window = case
    q = _rand(KEY, (B, 1, H, D), jnp.float32)
    kk = jax.random.fold_in(KEY, 11)
    k_pages = jax.random.normal(kk, (n_pool, page, Hkv, D))
    v_pages = jax.random.normal(jax.random.fold_in(kk, 1), (n_pool, page, Hkv, D))
    kq, ks = R.quantize_int8_ref(k_pages)
    vq, vs = R.quantize_int8_ref(v_pages)
    tbl = (jax.random.permutation(kk, n_pool)[: B * NP]
           .reshape(B, NP).astype(jnp.int32))
    valid = (jnp.arange(B, dtype=jnp.int32) * 7 % (NP * page)) + 2
    out = ops.paged_decode_attention_int8(
        q, kq, ks, vq, vs, tbl, valid, window=window, interpret=True
    )
    ref = R.paged_decode_attention_int8_ref(
        q, kq, ks, vq, vs, tbl, valid, window=window
    )
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


# ---------------------------------------------------------------------------
# fused MoE (dispatch + expert SwiGLU in one kernel)
# ---------------------------------------------------------------------------


def test_interpret_default_follows_the_backend(monkeypatch):
    assert ops.interpret_default() is (jax.default_backend() == "cpu")
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert ops.interpret_default() is False


def _moe_inputs(key, T, d, f, E):
    ks = jax.random.split(key, 5)
    x = jax.random.normal(ks[0], (T, d), jnp.float32)
    router = jax.random.normal(ks[1], (d, E)) * 0.5
    wg = jax.random.normal(ks[2], (E, d, f)) * 0.1
    wu = jax.random.normal(ks[3], (E, d, f)) * 0.1
    wo = jax.random.normal(ks[4], (E, f, d)) * 0.1
    return x, router, wg, wu, wo


FUSED_MOE_CASES = [
    # T, d, f, E, k, capacity
    (64, 32, 64, 8, 2, 32),            # no drops (T*k/E = 16 < C)
    (128, 16, 32, 4, 2, 128),          # multi-block capacity (C > block_c? no: =)
    (128, 32, 64, 8, 2, 8),            # heavy overflow: E*C=64 slots, 256 copies
    (96, 8, 16, 8, 1, 8),              # top-1
    (256, 64, 128, 16, 4, 256),        # two capacity blocks per expert
]


@pytest.mark.parametrize(
    "case", FUSED_MOE_CASES, ids=[str(c) for c in FUSED_MOE_CASES]
)
def test_fused_moe_matches_oracle(case):
    T, d, f, E, k, C = case
    x, router, wg, wu, wo = _moe_inputs(jax.random.fold_in(KEY, 21), T, d, f, E)
    out, aux = ops.fused_moe_mlp(
        x, router, wg, wu, wo, k=k, capacity=C, interpret=True
    )
    ref, aux_ref = R.fused_moe_mlp_ref(x, router, wg, wu, wo, k=k, capacity=C)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(aux, aux_ref, rtol=1e-6)


def test_fused_moe_capacity_overflow_drops_match_oracle():
    """capacity_factor < 1 territory: far fewer slots than token copies —
    the kernel must drop exactly the oracle's overflow copies."""
    T, d, f, E, k, C = 128, 32, 64, 4, 2, 8     # 256 copies, 32 slots
    x, router, wg, wu, wo = _moe_inputs(jax.random.fold_in(KEY, 22), T, d, f, E)
    out, aux = ops.fused_moe_mlp(
        x, router, wg, wu, wo, k=k, capacity=C, interpret=True
    )
    ref, _ = R.fused_moe_mlp_ref(x, router, wg, wu, wo, k=k, capacity=C)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)
    # sanity: overflow actually dropped copies (differs from uncapped run)
    uncapped, _ = R.fused_moe_mlp_ref(x, router, wg, wu, wo, k=k, capacity=T * k)
    assert float(jnp.abs(out - uncapped).max()) > 1e-3


def test_fused_moe_grad_matches_oracle():
    T, d, f, E, k, C = 64, 16, 32, 8, 2, 8
    x, router, wg, wu, wo = _moe_inputs(jax.random.fold_in(KEY, 23), T, d, f, E)

    def loss(fn, args):
        out, aux = fn(*args)
        return jnp.sum(out ** 2) + aux

    gk = jax.grad(lambda a: loss(
        lambda *t: ops.fused_moe_mlp(*t, k=k, capacity=C, interpret=True), a
    ))((x, router, wg, wu, wo))
    gr = jax.grad(lambda a: loss(
        lambda *t: R.fused_moe_mlp_ref(*t, k=k, capacity=C), a
    ))((x, router, wg, wu, wo))
    for got, want in zip(gk, gr):
        np.testing.assert_allclose(got, want, atol=2e-4, rtol=2e-4)


def test_fused_moe_matches_dense_model_path():
    """The kernel reproduces models/moe.py::_moe_mlp_dense (same routing,
    same capacity layout, same drops) — the wiring-level parity claim."""
    from repro.models import moe as M
    from repro.models.config import ModelConfig

    cfg = ModelConfig(family="moe", n_experts=8, experts_per_token=2,
                      d_model=32, d_ff=64, capacity_factor=0.5)
    B, S = 4, 32
    x = jax.random.normal(jax.random.fold_in(KEY, 24), (B, S, 32), jnp.float32)
    _, router, wg, wu, wo = _moe_inputs(jax.random.fold_in(KEY, 25), 1, 32, 64, 8)
    p = {"router": router, "wi_gate": wg, "wi_up": wu, "wo": wo}
    out_f, aux_f = M._moe_mlp_fused(p, x, cfg)
    out_d, aux_d = M._moe_mlp_dense(p, x, cfg)
    np.testing.assert_allclose(out_f, out_d, atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(aux_f, aux_d, rtol=1e-6)


# ---------------------------------------------------------------------------
# grouped matmul (dropless MoE)
# ---------------------------------------------------------------------------

# M rows, K, N, group sizes (summing to at most M; empty groups included)
GMM_CASES = [
    (64, 32, 48, (10, 0, 30, 24)),
    (200, 128, 128, (0, 77, 0, 0, 50)),      # rows past the last group
    (96, 16, 8, (0, 0, 0)),                  # no row routed here
]


@pytest.mark.parametrize("backend", ["cpu", "tpu"])
@pytest.mark.parametrize("case", GMM_CASES, ids=[str(c[3]) for c in GMM_CASES])
def test_grouped_matmul_and_grads_match_oracle(case, backend, monkeypatch):
    """Forward and both gradients against the oracle: on this backend's
    path (``lax.ragged_dot``) and on the TPU's (megablox ``gmm``/``tgmm``
    in the interpreter, rows padded to its 512-row tile)."""
    if backend == "tpu":
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        monkeypatch.setattr(ops, "interpret_default", lambda: True)
    M, K, N, sizes = case
    ks = jax.random.split(jax.random.fold_in(KEY, 40), 3)
    lhs = _rand(ks[0], (M, K), jnp.float32)
    rhs = _rand(ks[1], (len(sizes), K, N), jnp.float32) * K ** -0.5
    ct = _rand(ks[2], (M, N), jnp.float32)
    gs = jnp.array(sizes, jnp.int32)
    out = ops.grouped_matmul(lhs, rhs, gs)
    want = R.grouped_matmul_ref(lhs, rhs, gs)
    np.testing.assert_allclose(out, want, atol=1e-5, rtol=1e-5)
    assert not np.any(np.asarray(out[sum(sizes):]))
    got = jax.grad(lambda a, b: jnp.sum(ops.grouped_matmul(a, b, gs) * ct),
                   argnums=(0, 1))(lhs, rhs)
    ref = jax.grad(lambda a, b: jnp.sum(R.grouped_matmul_ref(a, b, gs) * ct),
                   argnums=(0, 1))(lhs, rhs)
    for g, r in zip(got, ref):
        np.testing.assert_allclose(g, r, atol=1e-4, rtol=1e-4)


# ---------------------------------------------------------------------------
# RG-LRU scan
# ---------------------------------------------------------------------------

RGLRU_CASES = [
    (2, 64, 128, jnp.float32),
    (1, 100, 300, jnp.float32),
    (3, 256, 64, jnp.float32),
    (1, 33, 96, jnp.bfloat16),
]


@pytest.mark.parametrize("case", RGLRU_CASES, ids=[str(c[:3]) for c in RGLRU_CASES])
def test_rglru_scan_matches_oracle(case):
    B, S, W, dtype = case
    ks = jax.random.split(KEY, 2)
    a = (jax.nn.sigmoid(jax.random.normal(ks[0], (B, S, W))) * 0.99).astype(dtype)
    x = _rand(ks[1], (B, S, W), dtype)
    out = ops.rglru_scan(a, x, chunk=32, interpret=True)
    ref = R.rglru_scan_ref(a, x)
    tol = 2e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(
        out.astype(jnp.float32), ref.astype(jnp.float32), atol=tol, rtol=tol
    )


def test_rglru_extreme_decay_stable():
    """Near-zero decays (log a ~ -150) must not overflow the chunked form."""
    B, S, W = 1, 64, 32
    a = jnp.full((B, S, W), 1e-30, jnp.float32)
    x = jnp.ones((B, S, W), jnp.float32)
    out = ops.rglru_scan(a, x, chunk=16, interpret=True)
    assert bool(jnp.all(jnp.isfinite(out)))
    np.testing.assert_allclose(out, R.rglru_scan_ref(a, x), atol=1e-5)


# ---------------------------------------------------------------------------
# RWKV6 scan
# ---------------------------------------------------------------------------

RWKV_CASES = [
    (2, 64, 2, 32, 16, jnp.float32),
    (1, 100, 4, 64, 32, jnp.float32),
    (2, 32, 2, 16, 32, jnp.float32),
    (1, 48, 2, 64, 16, jnp.bfloat16),
]


@pytest.mark.parametrize("case", RWKV_CASES, ids=[str(c[:5]) for c in RWKV_CASES])
def test_rwkv6_scan_matches_oracle(case):
    B, S, H, D, chunk, dtype = case
    ks = jax.random.split(KEY, 5)
    r = _rand(ks[0], (B, S, H, D), dtype) * 0.5
    k = _rand(ks[1], (B, S, H, D), dtype) * 0.5
    v = _rand(ks[2], (B, S, H, D), dtype) * 0.5
    w = jnp.exp(-jnp.exp(jax.random.normal(ks[3], (B, S, H, D)))).astype(dtype)
    u = _rand(ks[4], (H, D), jnp.float32) * 0.5
    out, s_fin = ops.rwkv6_scan(r, k, v, w, u, chunk=chunk, interpret=True)
    ref, s_ref = R.rwkv6_scan_ref(r, k, v, w, u)
    tol = 5e-5 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(
        out.astype(jnp.float32), ref.astype(jnp.float32), atol=tol, rtol=tol
    )
    np.testing.assert_allclose(s_fin, s_ref, atol=tol, rtol=tol)


def test_rwkv6_extreme_decay_stable():
    """w -> 0 (log w ~ -148 after the model's clip) must stay finite — the
    overflow-safe chunking claim."""
    B, S, H, D = 1, 64, 1, 16
    ks = jax.random.split(KEY, 4)
    r = _rand(ks[0], (B, S, H, D), jnp.float32)
    k = _rand(ks[1], (B, S, H, D), jnp.float32)
    v = _rand(ks[2], (B, S, H, D), jnp.float32)
    w = jnp.full((B, S, H, D), jnp.exp(-jnp.exp(5.0)), jnp.float32)  # ~e^-148
    u = jnp.zeros((H, D), jnp.float32)
    out, s = ops.rwkv6_scan(r, k, v, w, u, chunk=16, interpret=True)
    ref, s_ref = R.rwkv6_scan_ref(r, k, v, w, u)
    assert bool(jnp.all(jnp.isfinite(out)))
    np.testing.assert_allclose(out, ref, atol=1e-4, rtol=1e-4)


# ---------------------------------------------------------------------------
# int8 quantize
# ---------------------------------------------------------------------------


def test_quantize_matches_oracle():
    ks = jax.random.split(KEY, 2)
    x = jax.random.normal(ks[0], (100, 256)) * 3
    noise = jax.random.uniform(ks[1], (100, 256))
    q, s = ops.quantize_int8(x, noise, interpret=True)
    qr, sr = R.quantize_int8_ref(x, noise)
    assert bool(jnp.all(q == qr))
    np.testing.assert_allclose(s, sr, rtol=1e-6)  # 1-ulp division-order skew


def test_quantize_error_bounded_by_scale():
    x = jax.random.normal(KEY, (64, 128)) * 5
    noise = jax.random.uniform(jax.random.fold_in(KEY, 1), (64, 128))
    q, s = ops.quantize_int8(x, noise, interpret=True)
    err = jnp.abs(ops.dequantize_int8(q, s) - x)
    assert float(jnp.max(err - s)) <= 1e-6  # |err| <= scale (stochastic floor)


def test_dequantize_round_trip_matches_oracle():
    """dequantize(quantize(x)) agrees with the reference pair end to end."""
    ks = jax.random.split(jax.random.fold_in(KEY, 42), 2)
    x = jax.random.normal(ks[0], (48, 192)) * 2.5
    noise = jax.random.uniform(ks[1], (48, 192))
    q, s = ops.quantize_int8(x, noise, interpret=True)
    got = ops.dequantize_int8(q, s)
    want = R.dequantize_int8_ref(*R.quantize_int8_ref(x, noise))
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-7)


def test_dequantize_dtype_matches_oracle():
    x = jax.random.normal(KEY, (8, 64))
    noise = jax.random.uniform(jax.random.fold_in(KEY, 3), (8, 64))
    q, s = ops.quantize_int8(x, noise, interpret=True)
    got = ops.dequantize_int8(q, s, dtype=jnp.bfloat16)
    want = R.dequantize_int8_ref(q, s, dtype=jnp.bfloat16)
    assert got.dtype == jnp.bfloat16
    assert bool(jnp.all(got == want))


def test_quantize_stochastic_unbiased():
    """E[dequant(quant(x))] == x across noise draws."""
    x = jnp.full((1, 64), 0.3141, jnp.float32)
    outs = []
    for i in range(200):
        noise = jax.random.uniform(jax.random.fold_in(KEY, i), (1, 64))
        q, s = ops.quantize_int8(x, noise, interpret=True)
        outs.append(ops.dequantize_int8(q, s))
    mean = jnp.mean(jnp.stack(outs))
    assert abs(float(mean) - 0.3141) < 2e-3


@pytest.mark.parametrize("R_rows", [1, 5, 7, 100, 300, 511, 513])
@pytest.mark.parametrize("block_rows", [8, 256])
def test_quantize_ragged_rows_match_oracle(R_rows, block_rows):
    """Row counts not divisible by block_rows: the wrapper pads (sublane-
    aligned) and slices — every real row must still match the oracle."""
    ks = jax.random.split(jax.random.fold_in(KEY, R_rows), 2)
    x = jax.random.normal(ks[0], (R_rows, 40)) * 3
    noise = jax.random.uniform(ks[1], (R_rows, 40))
    q, s = ops.quantize_int8(x, noise, block_rows=block_rows, interpret=True)
    qr, sr = R.quantize_int8_ref(x, noise)
    assert q.shape == (R_rows, 40) and s.shape == (R_rows, 1)
    assert bool(jnp.all(q == qr))
    np.testing.assert_allclose(s, sr, rtol=1e-6)


@settings(max_examples=20, deadline=None)
@given(
    val=st.floats(min_value=-4.0, max_value=4.0,
                  allow_nan=False, allow_infinity=False),
    seed=st.integers(min_value=0, max_value=2**31 - 1),
)
def test_quantize_stochastic_rounding_unbiased_property(val, seed):
    """Property: E[dequantize(quantize(x))] ≈ x over noise seeds, for any
    magnitude — the error-feedback-free unbiasedness claim."""
    rows = jnp.linspace(-abs(val) - 1e-3, abs(val) + 1e-3, 32).reshape(1, 32)
    key = jax.random.PRNGKey(seed)
    acc = jnp.zeros_like(rows)
    n = 64
    for i in range(n):
        noise = jax.random.uniform(jax.random.fold_in(key, i), rows.shape)
        q, s = ops.quantize_int8(rows, noise, interpret=True)
        acc = acc + ops.dequantize_int8(q, s)
    mean = acc / n
    # per-element CI: one quantization step is `s`; mean of n uniform-floor
    # draws concentrates within ~s/sqrt(n) (4 sigma margin)
    step = float(s.max())
    np.testing.assert_allclose(mean, rows, atol=4 * step / np.sqrt(n) + 1e-6)


# ---------------------------------------------------------------------------
# q8 ops (int8-fused training: in-kernel dequant + int8 residuals)
# ---------------------------------------------------------------------------


def _q8_roundtrip(x):
    """Deterministic round-half-up quantize->dequantize, as the q8 ops do."""
    q, s = R.quantize_int8_ref(x, jnp.full(x.shape, 0.5, jnp.float32))
    return R.dequantize_int8_ref(q, s)


FLASH_Q8_CASES = [
    # B, S, H, Hkv, D, causal, window
    (2, 128, 4, 2, 64, True, None),
    (1, 100, 2, 2, 32, True, 32),
    (2, 64, 4, 4, 64, False, None),
]


@pytest.mark.parametrize("case", FLASH_Q8_CASES, ids=[str(c) for c in FLASH_Q8_CASES])
def test_flash_attention_q8_matches_oracle(case):
    B, S, H, Hkv, D, causal, window = case
    ks = jax.random.split(jax.random.fold_in(KEY, 31), 3)
    q = _rand(ks[0], (B, S, H, D), jnp.float32)
    k = _rand(ks[1], (B, S, Hkv, D), jnp.float32)
    v = _rand(ks[2], (B, S, Hkv, D), jnp.float32)
    out = ops.flash_attention_q8(
        q, k, v, causal=causal, window=window, block=64, interpret=True
    )
    ref = R.flash_attention_q8_ref(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)
    # the off-Pallas fallback IS the oracle, bit for bit
    fb = ops.flash_attention_q8(
        q, k, v, causal=causal, window=window, use_kernel=False
    )
    assert bool(jnp.all(fb == ref))


def test_flash_attention_q8_close_to_f32():
    """Documented tolerance of the int8-KV attention vs full precision."""
    ks = jax.random.split(jax.random.fold_in(KEY, 32), 3)
    q = _rand(ks[0], (2, 128, 4, 64), jnp.float32)
    k = _rand(ks[1], (2, 128, 4, 64), jnp.float32)
    v = _rand(ks[2], (2, 128, 4, 64), jnp.float32)
    out = ops.flash_attention_q8(q, k, v, causal=True, interpret=True)
    f32 = R.flash_attention_ref(q, k, v, causal=True)
    assert float(jnp.max(jnp.abs(out - f32))) < 5e-2


def test_flash_attention_q8_grad_matches_oracle():
    """Straight-through estimator: grads equal the base oracle's grads
    evaluated AT the dequantized K/V point (quantize has degenerate grads,
    so grad-of-q8-oracle is NOT the comparison)."""
    ks = jax.random.split(jax.random.fold_in(KEY, 33), 3)
    q = _rand(ks[0], (1, 64, 2, 32), jnp.float32)
    k = _rand(ks[1], (1, 64, 2, 32), jnp.float32)
    v = _rand(ks[2], (1, 64, 2, 32), jnp.float32)
    got = jax.grad(lambda t: ops.flash_attention_q8(
        *t, causal=True, interpret=True).sum())((q, k, v))
    kd, vd = _q8_roundtrip(k), _q8_roundtrip(v)
    want = jax.grad(lambda t: R.flash_attention_ref(
        *t, causal=True).sum())((q, kd, vd))
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, atol=2e-5, rtol=2e-5)


RWKV_Q8_CASES = [
    (2, 64, 2, 32, 16),
    (1, 100, 4, 64, 32),
]


@pytest.mark.parametrize("case", RWKV_Q8_CASES, ids=[str(c) for c in RWKV_Q8_CASES])
def test_rwkv6_scan_q8_matches_oracle(case):
    B, S, H, D, chunk = case
    ks = jax.random.split(jax.random.fold_in(KEY, 34), 5)
    r = _rand(ks[0], (B, S, H, D), jnp.float32) * 0.5
    k = _rand(ks[1], (B, S, H, D), jnp.float32) * 0.5
    v = _rand(ks[2], (B, S, H, D), jnp.float32) * 0.5
    w = jnp.exp(-jnp.exp(jax.random.normal(ks[3], (B, S, H, D))))
    u = _rand(ks[4], (H, D), jnp.float32) * 0.5
    out, s_fin = ops.rwkv6_scan_q8(r, k, v, w, u, chunk=chunk, interpret=True)
    ref, s_ref = R.rwkv6_scan_q8_ref(r, k, v, w, u)
    np.testing.assert_allclose(out, ref, atol=5e-5, rtol=5e-5)
    np.testing.assert_allclose(s_fin, s_ref, atol=5e-5, rtol=5e-5)
    fb_out, fb_s = ops.rwkv6_scan_q8(r, k, v, w, u, use_kernel=False)
    assert bool(jnp.all(fb_out == ref)) and bool(jnp.all(fb_s == s_ref))


def test_rwkv6_scan_q8_grad_matches_oracle():
    B, S, H, D = 1, 48, 2, 16
    ks = jax.random.split(jax.random.fold_in(KEY, 35), 5)
    r = _rand(ks[0], (B, S, H, D), jnp.float32) * 0.5
    k = _rand(ks[1], (B, S, H, D), jnp.float32) * 0.5
    v = _rand(ks[2], (B, S, H, D), jnp.float32) * 0.5
    w = jnp.exp(-jnp.exp(jax.random.normal(ks[3], (B, S, H, D))))
    u = _rand(ks[4], (H, D), jnp.float32) * 0.5

    def loss(fn, t):
        out, s = fn(t)
        return jnp.sum(out ** 2) + jnp.sum(s ** 2)

    got = jax.grad(lambda t: loss(
        lambda a: ops.rwkv6_scan_q8(*a, w, u, chunk=16, interpret=True), t
    ))((r, k, v))
    rd, kd, vd = _q8_roundtrip(r), _q8_roundtrip(k), _q8_roundtrip(v)
    want = jax.grad(lambda t: loss(
        lambda a: R.rwkv6_scan_ref(*a, w, u), t
    ))((rd, kd, vd))
    for g, x in zip(got, want):
        np.testing.assert_allclose(g, x, atol=2e-4, rtol=2e-4)


@pytest.mark.parametrize("case", [(2, 64, 128), (1, 100, 300)],
                         ids=["(2,64,128)", "(1,100,300)"])
def test_rglru_scan_q8_matches_oracle(case):
    B, S, W = case
    ks = jax.random.split(jax.random.fold_in(KEY, 36), 2)
    a = jax.nn.sigmoid(jax.random.normal(ks[0], (B, S, W))) * 0.99
    x = _rand(ks[1], (B, S, W), jnp.float32)
    out = ops.rglru_scan_q8(a, x, chunk=32, interpret=True)
    ref = R.rglru_scan_q8_ref(a, x)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)
    fb = ops.rglru_scan_q8(a, x, use_kernel=False)
    assert bool(jnp.all(fb == ref))


def test_rglru_scan_q8_grad_matches_oracle():
    B, S, W = 1, 64, 96
    ks = jax.random.split(jax.random.fold_in(KEY, 37), 2)
    a = jax.nn.sigmoid(jax.random.normal(ks[0], (B, S, W))) * 0.99
    x = _rand(ks[1], (B, S, W), jnp.float32)
    got = jax.grad(lambda t: ops.rglru_scan_q8(
        t[0], t[1], chunk=16, interpret=True).sum())((a, x))
    xd = _q8_roundtrip(x)
    want = jax.grad(lambda t: R.rglru_scan_ref(t[0], t[1]).sum())((a, xd))
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, atol=2e-5, rtol=2e-5)


# ---------------------------------------------------------------------------
# fused MoE combine (one-hot-matmul scatter-add)
# ---------------------------------------------------------------------------


def _combine_case(seed, T=64, d=32, E=8, k=2, C=8):
    from repro.kernels import fused_moe as FM

    ks = jax.random.split(jax.random.fold_in(KEY, seed), 3)
    x = jax.random.normal(ks[0], (T, d), jnp.float32)
    router = jax.random.normal(ks[1], (d, E)) * 0.5
    slot_tok, _gate, st, slot, keep, _aux = FM.moe_routing(x, router, k, C)
    y = jax.random.normal(ks[2], (E * C, d), jnp.float32)
    got = FM.fused_moe_combine(y, slot_tok, T, capacity=C, interpret=True)
    want = FM._combine_xla(y, st, slot, keep, T, E, C)
    assert bool(jnp.all(got == want)), f"combine not bit-exact (seed {seed})"


def test_fused_moe_combine_bitexact_vs_xla():
    """The one-hot-matmul combine is BIT-exact vs the XLA scatter-add:
    each token row receives <= k nonzero addends, and adding exact zeros is
    the identity in f32.  Includes heavy capacity overflow (dropped copies)."""
    _combine_case(41, C=32)          # no drops
    _combine_case(42, C=8)           # moderate overflow
    _combine_case(43, k=4, C=4)      # heavy overflow: most copies dropped
    _combine_case(44, T=100, d=48, E=4, k=1, C=16)  # ragged T vs block_t


@settings(max_examples=20, deadline=None)
@given(
    seed=st.integers(min_value=0, max_value=2 ** 31 - 1),
    k=st.integers(min_value=1, max_value=4),
    C=st.integers(min_value=1, max_value=48),
)
def test_fused_moe_combine_bitexact_property(seed, k, C):
    """Property form of the bit-exactness claim over random routings,
    top-k widths, and capacities (incl. overflow-drop regimes)."""
    _combine_case(seed % 1000 + 100, k=k, C=C)
