"""The dropless expert layer on a share of the experts (models/moe.py
``_moe_mlp_grouped``), at a small size on the CPU.

* The share identity: the parts that the shares of a split give add up to
  the whole layer's result, and each share's aux term is the whole's.
* Dropless: against a plain loop over tokens and their top-k experts, also
  under a router so skewed that a capacity of 1.25 would drop copies.
* The row counters: copies routed to the held experts, the rows the
  grouped matmul's tiles cover, and whether the layer took the static
  buffer.
* The compact buffer: where its C rows are fewer than the static buffer's
  T·min(k, held), the result and gradients are the loop's, through the
  compact rows when the copies routed here fit them and through the static
  buffer when a skewed router sends more; where C is the whole buffer, the
  layer is the static-buffer code bit for bit.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import smoke_config
from repro.kernels import ops as kops
from repro.models import moe as M

E, K, D, FF = 8, 2, 16, 32


def _cfg(**kw):
    return smoke_config("qwen3-moe-30b-a3b").with_(
        d_model=D, d_ff=FF, n_experts=E, experts_per_token=K, **kw)


def _layer(seed=0, skew=0.0, tokens=256):
    """Whole-layer params (all E experts) and tokens (4, tokens / 4, D).  With
    ``skew``, every token's logit for expert 0 is raised by about 2 x skew
    (the tokens are shifted so that each one's sum is positive)."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    router = jax.random.normal(ks[0], (D, E)) * D ** -0.5
    x = jax.random.normal(ks[4], (4, tokens // 4, D))
    if skew:
        x = x + 2.0
        router = router.at[:, 0].add(skew / D)
    p = {
        "router": router,
        "wi_gate": jax.random.normal(ks[1], (E, D, FF)) * D ** -0.5,
        "wi_up": jax.random.normal(ks[2], (E, D, FF)) * D ** -0.5,
        "wo": jax.random.normal(ks[3], (E, FF, D)) * FF ** -0.5,
    }
    return p, x


def _share(p, first, held):
    return dict(p, **{k: p[k][first:first + held]
                      for k in ("wi_gate", "wi_up", "wo")})


def _loop(p, x):
    """Plain per-token loop: each token's top-k experts, renormalised
    gates, SwiGLU, gate-weighted sum; nothing dropped."""
    xs = np.asarray(x, np.float64).reshape(-1, D)
    r, wg, wu, wo = (np.asarray(p[k], np.float64)
                     for k in ("router", "wi_gate", "wi_up", "wo"))
    out = np.zeros_like(xs)
    for t, xt in enumerate(xs):
        logits = xt @ r
        probs = np.exp(logits - logits.max())
        probs /= probs.sum()
        top = np.argsort(-probs)[:K]
        gates = probs[top] / probs[top].sum()
        for e, g in zip(top, gates):
            a, b = xt @ wg[e], xt @ wu[e]
            out[t] += g * ((a / (1 + np.exp(-a))) * b) @ wo[e]
    return out.reshape(x.shape)


def test_shares_add_up_to_the_whole_layer():
    p, x = _layer()
    whole, aux, rows = M.moe_mlp(p, x, _cfg(capacity_factor=None))
    parts, routed = 0.0, 0
    for first in range(0, E, 2):
        cfg = _cfg(capacity_factor=None, experts_held=2, first_expert=first)
        out, a, r = M.moe_mlp(_share(p, first, 2), x, cfg)
        parts = parts + out
        routed += int(r[0])
        np.testing.assert_allclose(a, aux, rtol=1e-6)
    np.testing.assert_allclose(parts, whole, rtol=1e-5, atol=1e-5)
    assert routed == int(rows[0]) == x.shape[0] * x.shape[1] * K


@pytest.mark.parametrize("skew", [0.0, 40.0])
def test_dropless_layer_matches_a_per_token_loop(skew):
    p, x = _layer(seed=1, skew=skew)
    out, _, rows = M.moe_mlp(p, x, _cfg(capacity_factor=None))
    np.testing.assert_allclose(out, _loop(p, x), rtol=1e-4, atol=1e-5)
    if skew:
        # expert 0 takes every token: more copies than a 1.25 capacity holds
        T = x.shape[0] * x.shape[1]
        ids = jax.lax.top_k(jax.nn.softmax(x.reshape(T, D) @ p["router"]),
                            K)[1]
        assert int(jnp.sum(ids == 0)) == T > M.expert_capacity(T, E, K, 1.25)
        capped, _, _ = M.moe_mlp(p, x, _cfg(capacity_factor=1.25))
        assert float(jnp.max(jnp.abs(capped - out))) > 1e-3


# (tokens, experts held, first held, skew): the static buffer's size (C =
# T·min(k, held)); a compact buffer of C = 1024 of 2048 rows, whose copies
# fit; the same with every token routed to the held expert (2048 > C)
SHARES = [(256, 3, 4, 0.0), (2048, 1, 0, 0.0), (2048, 1, 0, 40.0)]


@pytest.mark.parametrize("tokens,held,first,skew", SHARES)
def test_held_experts_only_compute_their_copies(tokens, held, first, skew):
    """A share's result is the loop's with the other experts' gates at 0."""
    p, x = _layer(seed=2, skew=skew, tokens=tokens)
    cfg = _cfg(capacity_factor=None, experts_held=held, first_expert=first)
    out, _, rows = M.moe_mlp(_share(p, first, held), x, cfg)
    keep = jnp.zeros(E).at[first:first + held].set(1.0)
    masked = dict(p, wo=p["wo"] * keep[:, None, None])
    np.testing.assert_allclose(out, _loop(masked, x), rtol=1e-4, atol=1e-5)
    C = M.compact_rows(tokens, K, held, E)
    assert int(rows[2]) == int(int(rows[0]) > C)
    if tokens == 2048:
        assert C == 1024 < tokens * min(K, held)
        assert int(rows[2]) == (skew > 0)


def test_rows_count_the_copies_and_the_tiles():
    gs = jnp.array([3, 0, 600, 1, 0, 509], jnp.int32)
    # 512-row tiles: [0,3) -> 1; [3,603) -> 2; [603,604) -> 1; [604,1113) -> 2
    assert int(M._tile_rows(gs, 512)) == 6 * 512
    p, x = _layer(seed=3)
    cfg = _cfg(capacity_factor=None, experts_held=4)
    _, _, rows = M.moe_mlp(_share(p, 0, 4), x, cfg)
    ids = jax.lax.top_k(jax.nn.softmax(x.reshape(-1, D) @ p["router"]), K)[1]
    assert int(rows[0]) == int(jnp.sum(ids < 4))
    assert int(rows[1]) >= int(rows[0]) and int(rows[1]) % 512 == 0
    assert int(rows[2]) == 0
    # twice a uniform router's copies, in 512-row tiles, at most T·min(k, held)
    assert M.compact_rows(32768, 8, 16, 128) == 65536
    assert M.compact_rows(2048, 2, 1, 8) == 1024
    assert M.compact_rows(256, 2, 4, 8) == M.compact_rows(256, 2, 8, 8) == 512


def test_capacity_paths_refuse_a_share():
    p, x = _layer()
    with pytest.raises(ValueError):
        M.moe_mlp(_share(p, 0, 4), x, _cfg(experts_held=4, capacity_factor=1.25))


def _plain(q, x, first, held):
    """Every held expert on every token, weighted by its renormalised gate
    (0 where it is not among the token's top k)."""
    T = x.shape[0] * x.shape[1]
    xs = x.reshape(T, D)
    probs = jax.nn.softmax(xs @ q["router"])
    gate, ids = jax.lax.top_k(probs, K)
    gate = gate / jnp.sum(gate, -1, keepdims=True)
    w = jnp.einsum("tk,tke->te", gate, jax.nn.one_hot(ids - first, held))
    h = jax.nn.silu(jnp.einsum("td,edf->tef", xs, q["wi_gate"])) * \
        jnp.einsum("td,edf->tef", xs, q["wi_up"])
    y = jnp.einsum("tef,efd->ted", h, q["wo"])
    return jnp.einsum("ted,te->td", y, w).reshape(x.shape)


@pytest.mark.parametrize("tokens,held,first,skew",
                         [(256, 4, 2, 0.0), SHARES[1]])
def test_grouped_layer_gradients_match_the_loop(tokens, held, first, skew):
    p, x = _layer(seed=4, skew=skew, tokens=tokens)
    cfg = _cfg(capacity_factor=None, experts_held=held, first_expert=first)
    share = _share(p, first, held)
    ct = jax.random.normal(jax.random.PRNGKey(9), x.shape)

    f = lambda q, x: jnp.sum(M.moe_mlp(q, x, cfg)[0] * ct)
    g = lambda q, x: jnp.sum(_plain(q, x, first, held) * ct)
    got = jax.grad(f, argnums=(0, 1))(share, x)
    want = jax.grad(g, argnums=(0, 1))(share, x)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)


def _static_buffer(p, x, cfg):
    """The static-buffer layer written out: every pass over the
    T·min(k, held) rows."""
    B, S, d = x.shape
    T, k, held = B * S, cfg.experts_per_token, cfg.resolved_experts_held()
    xf = x.reshape(T, d)
    probs = jax.nn.softmax(xf.astype(jnp.float32) @ p["router"])
    gate, expert = jax.lax.top_k(probs, k)
    gate = gate / jnp.sum(gate, axis=-1, keepdims=True)
    local = expert.reshape(-1) - cfg.first_expert
    is_held = (local >= 0) & (local < held)
    key = jnp.where(is_held, local, held)
    order = jnp.argsort(key, stable=True)
    inv = jnp.argsort(order)
    sizes = jnp.bincount(key, length=held + 1)[:held].astype(jnp.int32)
    Mr = T * min(k, held)
    xs = M._dispatch(xf, order[:Mr], inv, k)
    w_gu = jnp.concatenate([p["wi_gate"], p["wi_up"]], axis=-1)
    g, u = jnp.split(kops.grouped_matmul(xs, w_gu, sizes), 2, axis=-1)
    ys = kops.grouped_matmul(jax.nn.silu(g) * u, p["wo"], sizes)
    w = jnp.where(is_held.reshape(T, k), gate, 0.0)
    return M._combine(ys, w, inv, order[:Mr]).reshape(B, S, d)


@pytest.mark.parametrize("tokens,held,first,skew",
                         [(256, E, 0, 0.0), (256, 2, 6, 0.0), (256, 3, 4, 0.0),
                          (256, 4, 0, 0.0), SHARES[2]])
def test_static_buffer_paths_match_the_static_layer_bit_for_bit(tokens, held,
                                                               first, skew):
    """Where C is the whole buffer, and where the copies routed here do not
    fit C rows (the last case: the fallback), the layer's result and its
    gradients are the static-buffer code's, bit for bit."""
    p, x = _layer(seed=5, skew=skew, tokens=tokens)
    cfg = _cfg(capacity_factor=None, experts_held=held, first_expert=first)
    share = _share(p, first, held)
    ct = jax.random.normal(jax.random.PRNGKey(7), x.shape)
    out, _, rows = M.moe_mlp(share, x, cfg)
    assert int(rows[2]) == (skew > 0)
    np.testing.assert_array_equal(out, _static_buffer(share, x, cfg))
    got = jax.grad(lambda q, x: jnp.sum(M.moe_mlp(q, x, cfg)[0] * ct),
                   argnums=(0, 1))(share, x)
    want = jax.grad(lambda q, x: jnp.sum(_static_buffer(q, x, cfg) * ct),
                    argnums=(0, 1))(share, x)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_array_equal(a, b)
