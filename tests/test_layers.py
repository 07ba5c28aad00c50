"""Layer-level property tests: attention paths agree, RoPE invariants hold."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.models import layers as L

KEY = jax.random.PRNGKey(3)


@settings(max_examples=15, deadline=None)
@given(
    sq=st.integers(4, 48),
    skv=st.integers(4, 48),
    chunk=st.integers(3, 17),
    causal=st.booleans(),
    seed=st.integers(0, 1000),
)
def test_chunked_sdpa_matches_exact(sq, skv, chunk, causal, seed):
    """The flash-style chunked XLA path == exact sdpa for ANY chunking."""
    if causal and skv < sq:
        skv = sq
    key = jax.random.PRNGKey(seed)
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (1, sq, 2, 16))
    k = jax.random.normal(ks[1], (1, skv, 2, 16))
    v = jax.random.normal(ks[2], (1, skv, 2, 16))
    exact = L.sdpa(q, k, v, causal=causal)
    chunked = L.chunked_sdpa(q, k, v, causal=causal, chunk=chunk)
    np.testing.assert_allclose(exact, chunked, atol=2e-5, rtol=2e-5)


def test_rope_is_relative():
    """Attention logits depend only on position differences."""
    ks = jax.random.split(KEY, 2)
    q = jax.random.normal(ks[0], (1, 8, 1, 32))
    k = jax.random.normal(ks[1], (1, 8, 1, 32))

    def logits(offset):
        pos = jnp.arange(8) + offset
        qr = L.apply_rope(q, pos)
        kr = L.apply_rope(k, pos)
        return jnp.einsum("bqhd,bkhd->bqk", qr, kr)

    np.testing.assert_allclose(logits(0), logits(1000), atol=1e-3, rtol=1e-3)


def test_mrope_equals_rope_when_streams_equal():
    """Text tokens (all three M-RoPE streams equal) == standard RoPE."""
    x = jax.random.normal(KEY, (1, 8, 2, 24))
    pos = jnp.arange(8)[None]                  # (B, S)
    pos3 = jnp.broadcast_to(pos[None], (3, 1, 8))
    a = L.apply_mrope(x, pos3, sections=(4, 4, 4), theta=10000.0)
    b = L.apply_rope(x, pos[0], theta=10000.0)
    np.testing.assert_allclose(a, b, atol=1e-5)


def test_gqa_repeat_matches_explicit():
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (1, 8, 4, 16))
    k = jax.random.normal(ks[1], (1, 8, 2, 16))
    v = jax.random.normal(ks[2], (1, 8, 2, 16))
    gqa = L.sdpa(q, k, v, causal=True)
    mha = L.sdpa(q, jnp.repeat(k, 2, axis=2), jnp.repeat(v, 2, axis=2),
                 causal=True)
    np.testing.assert_allclose(gqa, mha, atol=1e-6)


def test_local_window_masks_far_keys():
    ks = jax.random.split(KEY, 3)
    S, W = 16, 4
    q = jax.random.normal(ks[0], (1, S, 1, 8))
    k = jax.random.normal(ks[1], (1, S, 1, 8))
    v = jax.random.normal(ks[2], (1, S, 1, 8))
    # zero out keys outside every window: result must be identical
    out1 = L.sdpa(q, k, v, causal=True, window=W)
    k2 = k.at[:, : S - W].set(jax.random.normal(ks[0], (1, S - W, 1, 8)))
    v2 = v.at[:, : S - W].set(jax.random.normal(ks[1], (1, S - W, 1, 8)))
    out2 = L.sdpa(q, k2, v2, causal=True, window=W)
    # positions >= W see only in-window keys, which are unchanged
    np.testing.assert_allclose(out1[:, S - 1], out2[:, S - 1], atol=1e-6)


def test_masked_softmax_rows_fully_masked_are_zero():
    """window+causal can fully mask early rows; output must be 0, not NaN."""
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (1, 4, 1, 8))
    k = jax.random.normal(ks[1], (1, 4, 1, 8))
    v = jax.random.normal(ks[2], (1, 4, 1, 8))
    out = L.chunked_sdpa(q, k, v, causal=True, window=1, q_offset=0, chunk=2)
    assert bool(jnp.all(jnp.isfinite(out)))


# ---------------------------------------------------------------------------
# the TPU training route: splash forward + dq/dkv backward (interpret mode)
# ---------------------------------------------------------------------------


def _rel(a, b):
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


# name -> (splash_route keyword changes, expected); the base case takes it
ROUTE_CASES = {
    "tpu-causal-aligned": ({}, True),
    "cpu-backend": ({"backend": "cpu"}, False),
    "non-causal": ({"causal": False}, False),
    "windowed": ({"window": 128}, False),
    "softcap": ({"softcap": 30.0}, False),
    "seq-not-a-tile": ({"q_shape": (2, 200, 8, 128), "k_shape": (2, 200, 2, 128)}, False),
    "cross-lengths": ({"k_shape": (2, 1024, 2, 128)}, False),
    "head-dim-64": ({"q_shape": (2, 512, 8, 64), "k_shape": (2, 512, 2, 64)}, False),
    "heads-not-grouped": ({"k_shape": (2, 512, 3, 128)}, False),
}


@pytest.mark.parametrize("case", sorted(ROUTE_CASES))
def test_splash_route_predicate(case):
    change, expected = ROUTE_CASES[case]
    args = dict(backend="tpu", q_shape=(2, 512, 8, 128),
                k_shape=(2, 512, 2, 128), causal=True, window=None,
                softcap=None)
    args.update(change)
    backend, q_shape, k_shape = (args.pop(a) for a in
                                 ("backend", "q_shape", "k_shape"))
    assert L.splash_route(backend, q_shape, k_shape, **args) is expected


def _attn_params(d_model, heads, kv_heads, head_dim=128):
    from repro.models.param import build

    params, _ = build(
        lambda b: L.init_attention(b, "attn", d_model, heads, kv_heads, head_dim),
        key=KEY, dtype=jnp.bfloat16)
    return params["attn"]


def _on_tpu_route(monkeypatch):
    """Make the backend read "tpu" to the route, with kernels interpreted."""
    from repro.kernels import ops

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(ops, "interpret_default", lambda: True)


def test_attention_train_takes_splash_on_tpu(monkeypatch):
    """attention_train on the TPU route: one tallied splash call whose result
    matches the CPU path's (chunked over f32 scores) to bf16 rounding."""
    p = _attn_params(256, 4, 2)
    x = jax.random.normal(KEY, (1, 1536, 256), jnp.bfloat16)
    pos = jnp.arange(x.shape[1])
    fn = jax.jit(lambda p, x: L.attention_train(p, x, positions=pos))
    with L.attention_path_tally() as cpu_paths:
        cpu = fn(p, x)
    _on_tpu_route(monkeypatch)
    with L.attention_path_tally() as tpu_paths:
        tpu = jax.jit(lambda p, x: L.attention_train(p, x, positions=pos))(p, x)
    assert cpu_paths == {"chunked": 1} and tpu_paths == {"splash": 1}
    assert _rel(tpu, cpu) < 2e-2


# name -> the call, which must not take the splash route on a TPU
BYPASS_CASES = {
    "non-causal": lambda p, x, pos: L.attention_train(p, x, positions=pos,
                                                      causal=False),
    "windowed": lambda p, x, pos: L.attention_train(p, x, positions=pos,
                                                    window=64),
    "int8-fused": lambda p, x, pos: L.attention_train(
        p, x, positions=pos, precision="int8-fused"),
    "prefill": lambda p, x, pos: L.attention_prefill(
        p, x, positions=pos, cache_len=x.shape[1])[0],
}


@pytest.mark.parametrize("case", sorted(BYPASS_CASES))
def test_tpu_route_leaves_other_attention_bit_identical(case, monkeypatch):
    p = _attn_params(128, 2, 2)
    x = jax.random.normal(KEY, (1, 256, 128), jnp.bfloat16)
    pos = jnp.arange(x.shape[1])
    call = BYPASS_CASES[case]
    before = jax.jit(lambda p, x: call(p, x, pos))(p, x)
    _on_tpu_route(monkeypatch)
    with L.attention_path_tally() as paths:
        after = jax.jit(lambda p, x: call(p, x, pos))(p, x)
    assert "splash" not in paths
    np.testing.assert_array_equal(np.asarray(after), np.asarray(before))


def _qk_attn_params(qk_norm, seed=5):
    from repro.models.param import build

    def f(b):
        L.init_attention(b, "attn", 32, 4, 2, 16, qk_norm=qk_norm)

    params, _ = build(f, key=jax.random.PRNGKey(seed))
    return params["attn"]


def test_qk_norm_is_a_per_head_rms_norm_before_rope():
    """q and k of each head are RMS-normed over head_dim with one scale of
    head_dim shared by the heads; v is not."""
    p = _qk_attn_params(True)
    assert p["q_norm"]["scale"].shape == p["k_norm"]["scale"].shape == (16,)
    ks = jax.random.split(KEY, 3)
    p = dict(p, q_norm={"scale": jax.random.uniform(ks[0], (16,)) + 0.5},
             k_norm={"scale": jax.random.uniform(ks[1], (16,)) + 0.5})
    x = jax.random.normal(ks[2], (2, 8, 32))
    q, k, v = L.qkv_project(p, x)

    def plain(w, scale):
        y = np.einsum("bsd,dhk->bshk", np.asarray(x, np.float64),
                      np.asarray(w, np.float64))
        rms = np.sqrt(np.mean(y * y, axis=-1, keepdims=True) + 1e-6)
        return y / rms * np.asarray(scale, np.float64)

    np.testing.assert_allclose(q, plain(p["wq"], p["q_norm"]["scale"]),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(k, plain(p["wk"], p["k_norm"]["scale"]),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(v, jnp.einsum("bsd,dhk->bshk", x, p["wv"]),
                               rtol=1e-6, atol=1e-6)


def test_qk_norm_off_leaves_attention_as_it_was():
    """Without the field there are no norm params and q, k, v are the bare
    projections, in training, prefill and decode alike."""
    p = _qk_attn_params(False)
    assert "q_norm" not in p and "k_norm" not in p
    x = jax.random.normal(KEY, (2, 8, 32))
    q, k, v = L.qkv_project(p, x)
    for t, w in ((q, "wq"), (k, "wk"), (v, "wv")):
        np.testing.assert_array_equal(
            t, jnp.einsum("bsd,dhk->bshk", x, p[w]))
    on = _qk_attn_params(True)
    on = dict(on, **{w: p[w] for w in ("wq", "wk", "wv", "wo")})
    pos = jnp.arange(8)
    off_out = L.attention_train(p, x, positions=pos)
    assert not np.allclose(L.attention_train(on, x, positions=pos), off_out)
    pre, cache = L.attention_prefill(p, x, positions=pos, cache_len=8)
    np.testing.assert_allclose(pre, off_out, rtol=1e-6, atol=1e-6)


def test_qk_norm_decode_matches_training_attention():
    """With QK-norm on, prefill of 7 tokens then one decode step give the
    training attention's last position."""
    p = _qk_attn_params(True)
    x = jax.random.normal(KEY, (2, 8, 32))
    full = L.attention_train(p, x, positions=jnp.arange(8))
    _, cache = L.attention_prefill(p, x[:, :7], positions=jnp.arange(7),
                                   cache_len=8)
    last, _ = L.attention_decode(p, x[:, 7:], cache,
                                 pos=jnp.full((2,), 7, jnp.int32))
    np.testing.assert_allclose(last[:, 0], full[:, 7], rtol=1e-5, atol=1e-5)
