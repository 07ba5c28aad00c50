"""Plain reference of the sparse-expert decoder family (Qwen3-MoE layout),
for training, on one chip's share of the experts.

Written from the published description of Qwen3-MoE (arXiv:2505.09388 and
the ``qwen3_moe`` config): pre-norm decoder, RMSNorm, grouped-query causal
attention with an RMSNorm over head_dim on each query and key head before
rotary position embedding (half-split pairs), and in every layer a routed
feed-forward: a router over all ``router_outputs`` experts, softmax, the
top ``num_experts_per_tok`` gates renormalised to sum to one, and SwiGLU
experts of width ``moe_intermediate_size``; untied output head.  It imports
nothing of the program under test.  The corpus, the group layout of a step,
AdamW, the learning-rate schedule and the int8 control's matrix product are
``dense.py``'s, loaded by path.

The chip's share: the file's ``num_experts`` experts are held here (the
first ones of the router's outputs); the layer adds what they give, and
what experts held elsewhere would add is left out, as on a chip of the
stated deployment.  The expert layer is the plainest form: every held
expert is applied to every token and weighted by its renormalised gate, or
by 0 where the token did not pick it.

The load-balancing term is the Switch form E * sum_e (count_e / T) * p_e of
each layer over all E router outputs (count_e: copies routed to e; p_e: mean
router probability of e; T: tokens of the step), summed over layers and
weighted by ``router_aux_loss_coef``.  HF's ``load_balancing_loss_func``
pools every layer's router outputs before taking the product; this keeps
one term per layer, as the program does.  The term is a product of batch
means, so the rows are taken in blocks: a forward pass over every block
first gives each layer's count_e / T for the whole batch, and with those
fixed each block's gradient is linear in its own router probabilities.

Everything is computed in float32 under ``default_matmul_precision
("highest")``; parameters are stored in the configuration's dtype between
steps.  ``precision="int8"`` is the control (see ``dense.py``).  Attention
is computed in blocks of queries, so that a row's scores over 4,096
positions fit.
"""
from __future__ import annotations

import importlib.util
import math
from functools import partial
from pathlib import Path
from typing import Any, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

PyTree = Any


def _load_dense():
    path = Path(__file__).with_name("dense.py")
    spec = importlib.util.spec_from_file_location("bench_reference_dense",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


_D = _load_dense()
seed_key = _D.seed_key
step_rows = _D.step_rows
padded_layout = _D.padded_layout
learning_rate = _D.learning_rate
param_dtype = _D.param_dtype

# queries per attention block: (1, 32, 512, 4096) f32 scores are 256 MiB
Q_BLOCK = 512


def dims(cfg: Dict) -> Dict[str, int]:
    d = int(cfg["hidden_size"])
    h = int(cfg["num_attention_heads"])
    return {
        "L": int(cfg["num_hidden_layers"]),
        "d": d,
        "H": h,
        "KV": int(cfg["num_key_value_heads"]),
        "hd": int(cfg.get("head_dim") or d // h),
        "ff": int(cfg["moe_intermediate_size"]),
        "E": int(cfg["router_outputs"]),
        "Eh": int(cfg["num_experts"]),
        "k": int(cfg["num_experts_per_tok"]),
        "V": int(cfg["vocab_size"]),
    }


# ---------------------------------------------------------------------------
# Weights: one jitted call from the seed, in the program's tree layout
# ---------------------------------------------------------------------------

# (path, shape builder, std builder; None for ones) in a fixed order: leaf i
# draws from fold_in(key, i)
_LEAVES = (
    (("embedding", "table"), lambda s: (s["V"], s["d"]), lambda s: 1.0),
    (("blocks", "ln1", "scale"), lambda s: (s["L"], s["d"]), None),
    (("blocks", "attn", "wq"), lambda s: (s["L"], s["d"], s["H"], s["hd"]),
     lambda s: s["d"] ** -0.5),
    (("blocks", "attn", "wk"), lambda s: (s["L"], s["d"], s["KV"], s["hd"]),
     lambda s: s["d"] ** -0.5),
    (("blocks", "attn", "wv"), lambda s: (s["L"], s["d"], s["KV"], s["hd"]),
     lambda s: s["d"] ** -0.5),
    (("blocks", "attn", "wo"), lambda s: (s["L"], s["H"], s["hd"], s["d"]),
     lambda s: (s["H"] * s["hd"]) ** -0.5),
    (("blocks", "attn", "q_norm", "scale"), lambda s: (s["L"], s["hd"]), None),
    (("blocks", "attn", "k_norm", "scale"), lambda s: (s["L"], s["hd"]), None),
    (("blocks", "ln2", "scale"), lambda s: (s["L"], s["d"]), None),
    (("blocks", "moe", "router"), lambda s: (s["L"], s["d"], s["E"]),
     lambda s: s["d"] ** -0.5),
    (("blocks", "moe", "wi_gate"), lambda s: (s["L"], s["Eh"], s["d"], s["ff"]),
     lambda s: s["d"] ** -0.5),
    (("blocks", "moe", "wi_up"), lambda s: (s["L"], s["Eh"], s["d"], s["ff"]),
     lambda s: s["d"] ** -0.5),
    (("blocks", "moe", "wo"), lambda s: (s["L"], s["Eh"], s["ff"], s["d"]),
     lambda s: s["ff"] ** -0.5),
    (("ln_f", "scale"), lambda s: (s["d"],), None),
    (("lm_head", "table"), lambda s: (s["V"], s["d"]), lambda s: s["d"] ** -0.5),
)


def init_params(cfg: Dict, key: jax.Array) -> PyTree:
    """Random weights from ``key``: N(0, 1) embeddings, N(0, 1/fan_in)
    matrices, unit norm scales; stored in the configuration's dtype."""
    s = dims(cfg)
    dt = param_dtype(cfg)
    tree: Dict = {}
    for i, (path, shape_fn, std_fn) in enumerate(_LEAVES):
        shape = shape_fn(s)
        if std_fn is None:
            leaf = jnp.ones(shape, dt)
        else:
            k = jax.random.fold_in(key, i)
            leaf = (jax.random.normal(k, shape, jnp.float32)
                    * std_fn(s)).astype(dt)
        node = tree
        for name in path[:-1]:
            node = node.setdefault(name, {})
        node[path[-1]] = leaf
    return tree


# ---------------------------------------------------------------------------
# Forward pass
# ---------------------------------------------------------------------------


def _attention(s, mm, q, k, v):
    """Causal attention over query blocks, each rematerialised in the
    backward pass: q (B,S,H,hd), k/v (B,S,H,hd) -> (B,S,H,hd)."""
    S = q.shape[1]
    qb = min(Q_BLOCK, S)

    def block(start):
        @jax.checkpoint
        def run(qs, k, v):
            scores = mm("bqhd,bkhd->bhqk", qs, k) / math.sqrt(s["hd"])
            causal = (np.arange(start, start + qs.shape[1])[:, None]
                      >= np.arange(S)[None, :])
            scores = jnp.where(causal[None, None], scores, -jnp.inf)
            return mm("bhqk,bkhd->bqhd", jax.nn.softmax(scores, axis=-1), v)
        return run(q[:, start:start + qb], k, v)

    return jnp.concatenate([block(i) for i in range(0, S, qb)], axis=1)


def _experts(s, mm, h, lp):
    """Every held expert on every token, weighted by its renormalised gate
    (0 where the token did not pick it).  Returns (out, router probs
    (B,S,E), copies each row routed to each of the E experts (B,E))."""
    B, S, _ = h.shape
    probs = jax.nn.softmax(mm("bsd,de->bse", h, lp["moe"]["router"]), axis=-1)
    top, ids = jax.lax.top_k(probs, s["k"])
    top = top / jnp.sum(top, axis=-1, keepdims=True)
    picked = jax.nn.one_hot(ids, s["E"], dtype=jnp.float32)   # (B,S,k,E)
    gates = jnp.einsum("bsk,bske->bse", top, picked)[..., : s["Eh"]]
    g = mm("bsd,edf->bsef", h, lp["moe"]["wi_gate"])
    u = mm("bsd,edf->bsef", h, lp["moe"]["wi_up"])
    y = mm("bsef,efd->bsed", jax.nn.silu(g) * u, lp["moe"]["wo"])
    out = jnp.einsum("bsed,bse->bsd", y, gates)
    return out, probs, jnp.sum(picked, axis=(1, 2))


def _layer(cfg: Dict, mm, x, lp):
    """One decoder layer: (new x, router probs (B,S,E), counts (B,E))."""
    s = dims(cfg)
    eps = float(cfg.get("rms_norm_eps", 1e-6))
    theta = float(cfg.get("rope_theta", 10000.0))
    rms = _D._rms
    h = rms(x, lp["ln1"]["scale"], eps)
    q = rms(mm("bsd,dhk->bshk", h, lp["attn"]["wq"]),
            lp["attn"]["q_norm"]["scale"], eps)
    k = rms(mm("bsd,dhk->bshk", h, lp["attn"]["wk"]),
            lp["attn"]["k_norm"]["scale"], eps)
    v = mm("bsd,dhk->bshk", h, lp["attn"]["wv"])
    q, k = _D._rope(q, theta), _D._rope(k, theta)
    rep = s["H"] // s["KV"]
    o = _attention(s, mm, q, jnp.repeat(k, rep, axis=2),
                   jnp.repeat(v, rep, axis=2))
    x = x + mm("bshk,hkd->bsd", o, lp["attn"]["wo"])
    y, probs, counts = _experts(s, mm, rms(x, lp["ln2"]["scale"], eps), lp)
    return x + y, probs, counts


def _forward(cfg, precision, params, tokens):
    """(final hidden states, [(probs, counts) of each layer])."""
    mm = _D._matmul(precision)
    p32 = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), params)
    x = p32["embedding"]["table"][tokens]
    layer = jax.checkpoint(partial(_layer, cfg, mm))
    routing = []
    for i in range(dims(cfg)["L"]):
        lp = jax.tree_util.tree_map(lambda a: a[i], p32["blocks"])
        x, probs, counts = layer(x, lp)
        routing.append((probs, counts))
    x = _D._rms(x, p32["ln_f"]["scale"], float(cfg.get("rms_norm_eps", 1e-6)))
    return mm, p32, x, routing


def route_counts(cfg: Dict, precision: str, params: PyTree,
                 tokens: jax.Array, in_batch: jax.Array) -> jax.Array:
    """(L, E) copies routed to each expert of each layer, over the rows
    that belong to the step (``in_batch`` 1)."""
    _, _, _, routing = _forward(cfg, precision, params, tokens)
    return jnp.stack([in_batch @ c for _, c in routing])


def loss_sum(cfg: Dict, precision: str, params: PyTree, tokens: jax.Array,
             labels: jax.Array, weight: jax.Array, in_batch: jax.Array,
             frac: jax.Array, aux_scale: jax.Array):
    """(sum over rows and positions of weight x next-token cross-entropy,
    the load-balancing surrogate): ``frac`` (L, E) is each layer's
    count_e / T over the whole step; the surrogate, aux_scale * sum over
    layers of E * sum_e frac_e * sum over the step's tokens in these rows of
    p_e, has the block's share of the aux term's gradient."""
    mm, p32, x, routing = _forward(cfg, precision, params, tokens)
    logits = mm("bsd,vd->bsv", x, p32["lm_head"]["table"])
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    ce = jnp.sum((lse - gold) * weight[:, None])
    E = dims(cfg)["E"]
    aux = sum(E * jnp.sum(frac[i] * jnp.einsum("b,bse->e", in_batch, probs))
              for i, (probs, _) in enumerate(routing))
    return ce, aux_scale * aux


# ---------------------------------------------------------------------------
# Training
# ---------------------------------------------------------------------------


def _grad_fn(cfg, precision, mesh):
    from jax.sharding import NamedSharding, PartitionSpec as P

    rows_sh = NamedSharding(mesh, P(None, "rows", None))
    w_sh = NamedSharding(mesh, P(None, "rows"))
    rep = NamedSharding(mesh, P())
    coef = float(cfg["router_aux_loss_coef"])

    def counts(params, tokens, in_batch):
        return jax.lax.map(
            lambda blk: route_counts(cfg, precision, params, *blk),
            (tokens, in_batch)).sum(axis=0)

    def grads(params, tokens, labels, weight, in_batch):
        # tokens (n_blocks, block_rows, S): one block's activations at a time
        n = jnp.maximum(jnp.sum(weight) * tokens.shape[-1], 1.0)
        T = jnp.sum(in_batch) * tokens.shape[-1]
        frac = jax.lax.stop_gradient(counts(params, tokens, in_batch)) / T
        # the objective is ce_sum / n + coef * aux: each block's surrogate
        # carries n so that the division below leaves coef * aux
        aux_scale = coef * n / T

        def body(carry, blk):
            tot, acc = carry
            (_, l), g = jax.value_and_grad(
                lambda p: sum_pair(loss_sum(cfg, precision, p, *blk, frac,
                                            aux_scale)),
                has_aux=True)(params)
            acc = jax.tree_util.tree_map(
                lambda a, b: a + b.astype(jnp.float32), acc, g)
            return (tot + l, acc), None

        acc0 = jax.tree_util.tree_map(
            lambda a: jnp.zeros(a.shape, jnp.float32), params)
        (tot, acc), _ = jax.lax.scan(
            body, (jnp.zeros((), jnp.float32), acc0),
            (tokens, labels, weight, in_batch))
        return tot / n, jax.tree_util.tree_map(lambda a: a / n, acc)

    return jax.jit(grads, in_shardings=(rep, rows_sh, rows_sh, w_sh, w_sh),
                   out_shardings=rep)


def sum_pair(pair):
    """(ce + surrogate, ce): the gradient of both, the value of the loss."""
    ce, aux = pair
    return ce + aux, ce


def _fits_on_device(cfg: Dict, device) -> bool:
    """Do params, f32 gradients (accumulator and one block's) and both f32
    AdamW moments fit in three quarters of one device's memory?"""
    s = dims(cfg)
    attn = s["d"] * s["hd"] * (s["H"] + 2 * s["KV"]) + s["H"] * s["hd"] * s["d"]
    n = (s["V"] * s["d"] * 2 + s["d"] + s["L"] * (
        attn + 2 * s["hd"] + 2 * s["d"] + s["d"] * s["E"]
        + 3 * s["Eh"] * s["d"] * s["ff"]))
    need = n * (param_dtype(cfg).itemsize + 4 * 4)
    try:
        limit = device.memory_stats()["bytes_limit"]
    except Exception:
        return True
    return need <= 0.75 * limit


def train_readings(
    cfg: Dict,
    traffic: Dict,
    seed: int,
    *,
    steps: int = 3,
    precision: str = "f32",
    fraction: float = 1.0,
    devices: Optional[Sequence] = None,
) -> Dict[str, Any]:
    """Run ``steps`` AdamW steps from the seed's weights on the seed's rows.

    Returns each step's loss (the masked mean cross-entropy), the norm of
    each leaf of the first gradient, and the norm of each leaf's change
    after ``steps`` steps.  ``fraction`` < 1 keeps only that leading share
    of each step's rows in the loss (a planted fault, for calibration); the
    router's term still spans every row of the step, as the program's
    does."""
    devices = list(devices or jax.devices())
    mesh = _D._row_mesh(devices)
    opt = traffic["optimizer"]
    opt_t = (float(opt["b1"]), float(opt["b2"]), float(opt["eps"]),
             float(opt["weight_decay"]))
    valid_rows = sum(b for _, b in traffic["groups"])
    on_device = _fits_on_device(cfg, devices[0])
    key = seed_key(seed)

    with jax.default_matmul_precision("highest"):
        from jax.sharding import NamedSharding, PartitionSpec as P

        rep = NamedSharding(mesh, P())
        init = jax.jit(partial(init_params, cfg), out_shardings=rep)
        params = _D.flat(init(key))
        grad_fn = _grad_fn(cfg, precision, mesh)
        moments: Dict[str, Any] = {}
        losses: List[float] = []
        grad_norms: Dict[str, float] = {}
        for t in range(steps):
            rows = step_rows(cfg, traffic, seed, t)
            toks, labs, w = _D._blocks(rows, len(devices), fraction)
            # rows of the step (the padding that fills the devices is not)
            _, _, in_batch = _D._blocks(rows, len(devices), 1.0)
            tree = _D._unflat(params)
            loss, grads = grad_fn(tree, toks, labs, w, in_batch)
            del tree
            losses.append(float(loss))
            grads = _D.flat(grads)
            if t == 0:
                grad_norms = {k: float(_D._norm(g)) for k, g in grads.items()}
            lr = learning_rate(traffic["schedule"], valid_rows, t)
            for k in list(params):
                if k in moments:
                    m, v = moments.pop(k)
                    if not on_device:
                        m, v = jax.device_put(m, rep), jax.device_put(v, rep)
                else:
                    m = jnp.zeros(params[k].shape, jnp.float32, device=rep)
                    v = jnp.zeros(params[k].shape, jnp.float32, device=rep)
                p, m, v = _D._adamw_leaf(opt_t, float(t + 1), params[k], m, v,
                                         grads.pop(k), lr)
                params[k] = p
                moments[k] = (m, v) if on_device else (
                    np.asarray(m), np.asarray(v))
            del grads
        moments.clear()
        start = _D.flat(init(key))
        change = {k: float(_D._diff_norm(params[k], start[k]))
                  for k in params}
    return {"losses": losses, "grad_norms": grad_norms, "change_norms": change}
