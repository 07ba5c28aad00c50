"""The dropless expert layer on a share of the experts (models/moe.py
``_moe_mlp_grouped``), at a small size on the CPU.

* The share identity: the parts that the shares of a split give add up to
  the whole layer's result, and each share's aux term is the whole's.
* Dropless: against a plain loop over tokens and their top-k experts, also
  under a router so skewed that a capacity of 1.25 would drop copies.
* The row counters: copies routed to the held experts, and the rows the
  grouped matmul's tiles cover.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import smoke_config
from repro.models import moe as M

E, K, D, FF = 8, 2, 16, 32


def _cfg(**kw):
    return smoke_config("qwen3-moe-30b-a3b").with_(
        d_model=D, d_ff=FF, n_experts=E, experts_per_token=K, **kw)


def _layer(seed=0, skew=0.0):
    """Whole-layer params (all E experts) and tokens (4, 64, D).  With
    ``skew``, every token's logit for expert 0 is raised by about 2 x skew
    (the tokens are shifted so that each one's sum is positive)."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    router = jax.random.normal(ks[0], (D, E)) * D ** -0.5
    x = jax.random.normal(ks[4], (4, 64, D))
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


def test_held_experts_only_compute_their_copies():
    """A share's result is the loop's with the other experts' gates at 0."""
    p, x = _layer(seed=2)
    cfg = _cfg(capacity_factor=None, experts_held=3, first_expert=4)
    out, _, _ = M.moe_mlp(_share(p, 4, 3), x, cfg)
    masked = dict(p, wo=p["wo"].at[:4].set(0).at[7:].set(0))
    np.testing.assert_allclose(out, _loop(masked, x), rtol=1e-4, atol=1e-5)


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


def test_capacity_paths_refuse_a_share():
    p, x = _layer()
    with pytest.raises(ValueError):
        M.moe_mlp(_share(p, 0, 4), x, _cfg(experts_held=4, capacity_factor=1.25))


def test_grouped_layer_gradients_match_the_loop():
    p, x = _layer(seed=4)
    cfg = _cfg(capacity_factor=None, experts_held=4, first_expert=2)
    share = _share(p, 2, 4)
    ct = jax.random.normal(jax.random.PRNGKey(9), x.shape)

    def plain(q, x):
        T = x.shape[0] * x.shape[1]
        xs = x.reshape(T, D)
        probs = jax.nn.softmax(xs @ q["router"])
        gate, ids = jax.lax.top_k(probs, K)
        gate = gate / jnp.sum(gate, -1, keepdims=True)
        w = jnp.einsum("tk,tke->te", gate, jax.nn.one_hot(ids - 2, 4))
        h = jax.nn.silu(jnp.einsum("td,edf->tef", xs, q["wi_gate"])) * \
            jnp.einsum("td,edf->tef", xs, q["wi_up"])
        y = jnp.einsum("tef,efd->ted", h, q["wo"])
        return jnp.einsum("ted,te->td", y, w).reshape(x.shape)

    f = lambda q, x: jnp.sum(M.moe_mlp(q, x, cfg)[0] * ct)
    g = lambda q, x: jnp.sum(plain(q, x) * ct)
    got = jax.grad(f, argnums=(0, 1))(share, x)
    want = jax.grad(g, argnums=(0, 1))(share, x)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)
