"""Plain reference of the dense decoder family (llama layout), for training.

Written from the published description of the architecture (pre-norm
decoder, RMSNorm, rotary position embedding on half-split pairs, grouped-query
causal attention, SwiGLU feed-forward, untied output head) and of AdamW.  It
imports nothing of the program under test: the token generator below is a
copy of the synthetic corpus's definition, and the batches are derived from
the traffic file's group layout, not read from the program's feed.

Everything is computed in float32 under ``default_matmul_precision
("highest")``.  Parameters are stored in the configuration's dtype (bfloat16)
between steps, as the configuration states, so a value that an update moves
by less than half a bfloat16 spacing stays put on both sides.

``precision="int8"`` is the control, one precision step below the bfloat16
the configurations state: every matrix product takes operands rounded to
int8 with one scale per tensor, in the forward and the backward pass, and
accumulates in float32.

The weights the program trains are made by :func:`init_params` too (the
harness jits it with the program's shardings), so the reference regenerates
them from the seed and takes nothing the program made.
"""
from __future__ import annotations

import math
import zlib
from functools import partial
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

PyTree = Any

# ---------------------------------------------------------------------------
# Shapes from the configuration file
# ---------------------------------------------------------------------------


def dims(cfg: Dict) -> Dict[str, int]:
    d = int(cfg["hidden_size"])
    h = int(cfg["num_attention_heads"])
    return {
        "L": int(cfg["num_hidden_layers"]),
        "d": d,
        "H": h,
        "KV": int(cfg["num_key_value_heads"]),
        "hd": int(cfg.get("head_dim") or d // h),
        "ff": int(cfg["intermediate_size"]),
        "V": int(cfg["vocab_size"]),
    }


def param_dtype(cfg: Dict):
    return jnp.dtype(cfg.get("torch_dtype", "float32"))


# ---------------------------------------------------------------------------
# Weights: one jitted call from the seed, in the program's tree layout
# ---------------------------------------------------------------------------

# (path, shape builder, std builder; None for ones) in a fixed order: leaf i draws
# from fold_in(key, i)
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
    (("blocks", "ln2", "scale"), lambda s: (s["L"], s["d"]), None),
    (("blocks", "mlp", "wi_gate"), lambda s: (s["L"], s["d"], s["ff"]),
     lambda s: s["d"] ** -0.5),
    (("blocks", "mlp", "wi_up"), lambda s: (s["L"], s["d"], s["ff"]),
     lambda s: s["d"] ** -0.5),
    (("blocks", "mlp", "wo"), lambda s: (s["L"], s["ff"], s["d"]),
     lambda s: s["ff"] ** -0.5),
    (("ln_f", "scale"), lambda s: (s["d"],), None),
    (("lm_head", "table"), lambda s: (s["V"], s["d"]), lambda s: s["d"] ** -0.5),
)


def seed_key(seed: int) -> jax.Array:
    """A raw threefry key holding all 64 bits of ``seed``."""
    seed = int(seed)
    return jnp.asarray(
        np.array([(seed >> 32) & 0xFFFFFFFF, seed & 0xFFFFFFFF], np.uint32)
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


def flat(tree: PyTree) -> Dict[str, jax.Array]:
    """{"a/b/c": leaf} view of a nested-dict tree."""
    out: Dict[str, jax.Array] = {}

    def walk(node, prefix):
        if isinstance(node, dict):
            for k in sorted(node):
                walk(node[k], prefix + (k,))
        else:
            out["/".join(prefix)] = node

    walk(tree, ())
    return out


# ---------------------------------------------------------------------------
# The data: a copy of the synthetic corpus and the group layout of a step
# ---------------------------------------------------------------------------


def synth_sequence(seed: int, vocab: int, seq_len: int, shard_id: str,
                   index: int, zipf_a: float = 1.2) -> np.ndarray:
    """Sample ``index`` of shard ``shard_id``: (seq_len + 1,) int32 ids.

    Zipf unigram ids folded into the lowest quarter of the vocabulary, plus
    a positional drift of period 13 whose step depends on the shard."""
    h = zlib.crc32(shard_id.encode()) & 0x7FFFFFFF
    rng = np.random.default_rng(np.array([seed, h, index], np.uint64))
    z = rng.zipf(zipf_a, size=seq_len + 1).astype(np.int64)
    base = z % max(2, vocab // 4)
    drift = (np.arange(seq_len + 1, dtype=np.int64) * (h % 97 + 1)) % 13
    return ((base + drift) % vocab).astype(np.int32)


def shard_of(worker: str, traffic: Dict) -> str:
    """The shard a worker's first samples come from: its own private shard
    where its class holds one (private data never leaves its owner), else
    the shared public pool, read from its start."""
    cls = worker.rsplit("/", 1)[0]
    if traffic["shards"].get("private_per_worker", {}).get(cls, 0) > 0:
        return f"private-{worker}"
    return "public"


def step_rows(cfg: Dict, traffic: Dict, seed: int, step: int) -> np.ndarray:
    """The valid rows of training step ``step`` (0-based), in group order:
    (n_valid, seq_len + 1) int32."""
    seq, vocab = int(traffic["seq_len"]), dims(cfg)["V"]
    rows = []
    for worker, b in traffic["groups"]:
        shard = shard_of(worker, traffic)
        cap = (traffic["shards"]["private_per_worker"].get(
            worker.rsplit("/", 1)[0], 0) if shard != "public"
            else traffic["shards"]["public"])
        for r in range(b):
            idx = step * b + r
            if idx >= cap:
                raise ValueError(f"{worker} would wrap its shard at step {step}")
            rows.append(synth_sequence(seed, vocab, seq, shard, idx))
    return np.stack(rows)


def padded_layout(traffic: Dict) -> Tuple[int, np.ndarray]:
    """(max_local, validity of each padded row) of the masked global batch."""
    batches = [b for _, b in traffic["groups"]]
    ml = max(batches)
    valid = np.zeros((len(batches), ml), bool)
    for g, b in enumerate(batches):
        valid[g, :b] = True
    return ml, valid.reshape(-1)


# ---------------------------------------------------------------------------
# Forward pass and loss
# ---------------------------------------------------------------------------


def _round_i8(x: jax.Array) -> jax.Array:
    """Round to int8 with one scale for the whole tensor (absmax / 127)."""
    amax = jnp.max(jnp.abs(x))
    scale = jnp.where(amax > 0, amax / 127.0, 1.0)
    return jnp.clip(jnp.round(x / scale), -127, 127) * scale


@partial(jax.custom_vjp, nondiff_argnums=(0,))
def _mm_i8(spec: str, a: jax.Array, b: jax.Array) -> jax.Array:
    return jnp.einsum(spec, _round_i8(a), _round_i8(b))


def _mm_i8_fwd(spec, a, b):
    return _mm_i8(spec, a, b), (a, b)


def _mm_i8_bwd(spec, res, g):
    a, b = res
    _, vjp = jax.vjp(lambda x, y: jnp.einsum(spec, x, y),
                     _round_i8(a), _round_i8(b))
    return vjp(_round_i8(g))


_mm_i8.defvjp(_mm_i8_fwd, _mm_i8_bwd)


def _matmul(precision: str):
    if precision == "int8":
        return lambda spec, a, b: _mm_i8(spec, a, b)
    if precision != "f32":
        raise ValueError(f"unknown precision {precision!r}")
    return jnp.einsum


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _rope(x, theta):
    """Rotary embedding on (first half, second half) pairs: x (B,S,H,D)."""
    S, D = x.shape[1], x.shape[-1]
    inv = 1.0 / (theta ** (np.arange(0, D, 2, dtype=np.float64) / D))
    ang = np.arange(S, dtype=np.float64)[:, None] * inv[None, :]
    cos = jnp.asarray(np.cos(ang), jnp.float32)[None, :, None, :]
    sin = jnp.asarray(np.sin(ang), jnp.float32)[None, :, None, :]
    x1, x2 = x[..., : D // 2], x[..., D // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _layer(cfg: Dict, mm, x, lp):
    s = dims(cfg)
    eps = float(cfg.get("rms_norm_eps", 1e-6))
    theta = float(cfg.get("rope_theta", 10000.0))
    B, S, _ = x.shape
    h = _rms(x, lp["ln1"]["scale"], eps)
    q = _rope(mm("bsd,dhk->bshk", h, lp["attn"]["wq"]), theta)
    k = _rope(mm("bsd,dhk->bshk", h, lp["attn"]["wk"]), theta)
    v = mm("bsd,dhk->bshk", h, lp["attn"]["wv"])
    rep = s["H"] // s["KV"]
    k = jnp.repeat(k, rep, axis=2)
    v = jnp.repeat(v, rep, axis=2)
    scores = mm("bqhd,bkhd->bhqk", q, k) / math.sqrt(s["hd"])
    causal = np.tril(np.ones((S, S), bool))
    scores = jnp.where(causal[None, None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    o = mm("bhqk,bkhd->bqhd", probs, v)
    x = x + mm("bshk,hkd->bsd", o, lp["attn"]["wo"])
    h = _rms(x, lp["ln2"]["scale"], eps)
    g = mm("bsd,df->bsf", h, lp["mlp"]["wi_gate"])
    u = mm("bsd,df->bsf", h, lp["mlp"]["wi_up"])
    return x + mm("bsf,fd->bsd", jax.nn.silu(g) * u, lp["mlp"]["wo"])


def loss_sum(cfg: Dict, precision: str, params: PyTree, tokens: jax.Array,
             labels: jax.Array, weight: jax.Array) -> jax.Array:
    """Sum over rows and positions of weight x next-token cross-entropy."""
    mm = _matmul(precision)
    p32 = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), params)
    x = p32["embedding"]["table"][tokens]
    layer = jax.checkpoint(partial(_layer, cfg, mm))
    for i in range(dims(cfg)["L"]):
        lp = jax.tree_util.tree_map(lambda a: a[i], p32["blocks"])
        x = layer(x, lp)
    x = _rms(x, p32["ln_f"]["scale"], float(cfg.get("rms_norm_eps", 1e-6)))
    logits = mm("bsd,vd->bsv", x, p32["lm_head"]["table"])
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.sum((lse - gold) * weight[:, None])


# ---------------------------------------------------------------------------
# Training: masked mean loss, gradients, AdamW, the learning-rate schedule
# ---------------------------------------------------------------------------


def learning_rate(schedule: Dict, valid_rows: int, step: int) -> float:
    """Linear-scaling warmup (Goyal et al.): from base_lr to
    base_lr * valid_rows / base_batch over warmup_steps, then linear decay
    to final_frac of that peak by total_steps."""
    base = float(schedule["base_lr"])
    peak = base * valid_rows / float(schedule["base_batch"])
    warm_n = max(1, int(schedule["warmup_steps"]))
    total = int(schedule["total_steps"])
    if step < int(schedule["warmup_steps"]):
        return base + (peak - base) * min(step / warm_n, 1.0)
    frac = min(max((step - int(schedule["warmup_steps"]))
                   / max(1, total - int(schedule["warmup_steps"])), 0.0), 1.0)
    return peak * (1.0 - (1.0 - float(schedule.get("final_frac", 0.1))) * frac)


@partial(jax.jit, static_argnums=(0,), donate_argnums=(2, 3, 4))
def _adamw_leaf(opt: Tuple, t, p, m, v, g, lr):
    b1, b2, eps, wd = opt
    t = jnp.asarray(t, jnp.float32)
    c1 = 1.0 - b1 ** t
    c2 = 1.0 - b2 ** t
    m = b1 * m + (1.0 - b1) * g
    v = b2 * v + (1.0 - b2) * g * g
    p32 = p.astype(jnp.float32)
    step = (m / c1) / (jnp.sqrt(v / c2) + eps) + wd * p32
    return (p32 - lr * step).astype(p.dtype), m, v


@jax.jit
def _norm(x):
    return jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))


@jax.jit
def _diff_norm(a, b):
    return jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32)
                                       - b.astype(jnp.float32))))


def _row_mesh(devices):
    from jax.sharding import Mesh

    return Mesh(np.asarray(devices), ("rows",))


def _grad_fn(cfg, precision, mesh):
    from jax.sharding import NamedSharding, PartitionSpec as P

    rows_sh = NamedSharding(mesh, P(None, "rows", None))
    w_sh = NamedSharding(mesh, P(None, "rows"))
    rep = NamedSharding(mesh, P())

    def grads(params, tokens, labels, weight):
        # tokens (n_blocks, block_rows, S): one block's activations at a time
        def body(carry, blk):
            tot, acc = carry
            l, g = jax.value_and_grad(
                lambda p: loss_sum(cfg, precision, p, *blk)
            )(params)
            acc = jax.tree_util.tree_map(
                lambda a, b: a + b.astype(jnp.float32), acc, g)
            return (tot + l, acc), None

        acc0 = jax.tree_util.tree_map(
            lambda a: jnp.zeros(a.shape, jnp.float32), params)
        (tot, acc), _ = jax.lax.scan(
            body, (jnp.zeros((), jnp.float32), acc0), (tokens, labels, weight))
        n = jnp.maximum(jnp.sum(weight) * tokens.shape[-1], 1.0)
        return tot / n, jax.tree_util.tree_map(lambda a: a / n, acc)

    return jax.jit(grads, in_shardings=(rep, rows_sh, rows_sh, w_sh),
                   out_shardings=rep)


def _blocks(rows: np.ndarray, n_dev: int, fraction: float):
    """Split valid rows into blocks of one row per device; rows past
    ``fraction`` of the batch (and padding) get weight 0."""
    n = rows.shape[0]
    keep = max(1, int(round(n * fraction)))
    n_pad = (-n) % n_dev
    rows = np.concatenate([rows, np.zeros((n_pad, rows.shape[1]), np.int32)])
    w = np.zeros(rows.shape[0], np.float32)
    w[:keep] = 1.0
    nb = rows.shape[0] // n_dev
    toks = rows[:, :-1].reshape(nb, n_dev, -1)
    labs = rows[:, 1:].reshape(nb, n_dev, -1)
    return toks, labs, w.reshape(nb, n_dev)


def _fits_on_device(cfg: Dict, device) -> bool:
    """Do params, f32 gradients (accumulator and one block's) and both f32
    AdamW moments fit in three quarters of one device's memory?"""
    s = dims(cfg)
    n = (s["V"] * s["d"] * 2 + s["L"] * (
        s["d"] * s["hd"] * (s["H"] + 2 * s["KV"]) + s["H"] * s["hd"] * s["d"]
        + 3 * s["d"] * s["ff"] + 2 * s["d"]) + s["d"])
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

    Returns each step's loss, the norm of each leaf of the first gradient,
    and the norm of each leaf's change after ``steps`` steps.  ``fraction``
    < 1 keeps only that leading share of each step's rows (a planted
    fault, for calibration).  AdamW moments stay on the device where they
    fit and are parked on the host between steps where they do not."""
    devices = list(devices or jax.devices())
    mesh = _row_mesh(devices)
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
        params = flat(init(key))
        grad_fn = _grad_fn(cfg, precision, mesh)
        moments: Dict[str, Any] = {}
        losses: List[float] = []
        grad_norms: Dict[str, float] = {}
        for t in range(steps):
            rows = step_rows(cfg, traffic, seed, t)
            toks, labs, w = _blocks(rows, len(devices), fraction)
            tree = _unflat(params)
            loss, grads = grad_fn(tree, toks, labs, w)
            del tree
            losses.append(float(loss))
            grads = flat(grads)
            if t == 0:
                grad_norms = {k: float(_norm(g)) for k, g in grads.items()}
            lr = learning_rate(traffic["schedule"], valid_rows, t)
            for k in list(params):
                if k in moments:
                    m, v = moments.pop(k)
                    if not on_device:
                        m, v = jax.device_put(m, rep), jax.device_put(v, rep)
                else:
                    m = jnp.zeros(params[k].shape, jnp.float32, device=rep)
                    v = jnp.zeros(params[k].shape, jnp.float32, device=rep)
                p, m, v = _adamw_leaf(opt_t, float(t + 1), params[k], m, v,
                                      grads.pop(k), lr)
                params[k] = p
                moments[k] = (m, v) if on_device else (
                    np.asarray(m), np.asarray(v))
            del grads
        moments.clear()
        start = flat(init(key))
        change = {k: float(_diff_norm(params[k], start[k])) for k in params}
    return {"losses": losses, "grad_norms": grad_norms, "change_norms": change}


def _unflat(leaves: Dict[str, Any]) -> PyTree:
    tree: Dict = {}
    for path, leaf in leaves.items():
        node = tree
        parts = path.split("/")
        for name in parts[:-1]:
            node = node.setdefault(name, {})
        node[parts[-1]] = leaf
    return tree
