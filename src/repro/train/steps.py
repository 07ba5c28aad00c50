"""train_step / serve_step factories: the functions that get pjit'd.

``make_train_step`` builds the masked-weighted-loss training step
(:mod:`repro.core.hetero` semantics): per-token CE, multiplied by the combined
row-validity x token mask, summed and normalized GLOBALLY, so heterogeneous
group batch sizes are numerically exact.  ``make_serve_step`` builds the
one-token KV-cache decode step for the inference shapes.

This module also owns the *abstract* train state (ShapeDtypeStruct trees for
params / opt_state / batch — no allocation) and :func:`build_sharding_plan`,
which resolves the logical-axis rule table against a live mesh into the
:class:`~repro.api.artifacts.ShardingPlan` every downstream consumer
(``Session.compile``, sharded init, meshfeed, checkpoint restore) reads.
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.hetero import masked_mean_loss
from repro.distributed.sharding import (
    ShardingPlan, arg_shardings_for_tree, make_rules,
)
from repro.models.api import Model
from repro.optim.optimizers import Optimizer, OptState

PyTree = Any

# logical axes of the Stannis training batch: rows over the dp-ish axes,
# sequence replicated (SP long-context shards it via the seq_data rule)
BATCH_AXES: Dict[str, Tuple[Optional[str], ...]] = {
    "tokens": ("batch", "seq_data"),
    "labels": ("batch", "seq_data"),
    "loss_mask": ("batch", "seq_data"),
}


def cross_entropy(logits: jax.Array, labels: jax.Array) -> jax.Array:
    """Per-token CE, numerically stable. logits (B,S,V) f32/bf16; labels (B,S)."""
    logits = logits.astype(jnp.float32)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return lse - gold


def loss_fn(
    model: Model,
    params: PyTree,
    batch: Dict[str, jax.Array],
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Masked global-mean LM loss (+ the router aux for MoE, weighted by
    ``router_aux_weight`` of the model's config); the parts also carry the
    forward's counters (``Model.forward_with_stats``)."""
    kwargs = {}
    for k in ("frames", "patch_embeds"):
        if k in batch:
            kwargs[k] = batch[k]
    logits, aux, stats = model.forward_with_stats(
        params, batch["tokens"], **kwargs)
    # VLM: logits cover [patches | text]; score text positions only
    labels = batch["labels"]
    mask = batch["loss_mask"]
    if logits.shape[1] != labels.shape[1]:
        logits = logits[:, -labels.shape[1]:]
    with jax.named_scope("vocab"):
        ce = cross_entropy(logits, labels)
        loss = masked_mean_loss(ce, mask)
    total = loss + model.cfg.router_aux_weight * aux
    return total, {"loss": loss, "aux": aux, "tokens": jnp.sum(mask), **stats}


def make_train_step(
    model: Model,
    optimizer: Optimizer,
    lr_schedule: Callable[[jax.Array], jax.Array],
    *,
    grad_transform: Optional[Callable[[PyTree], PyTree]] = None,
) -> Callable:
    """Returns ``train_step(params, opt_state, batch) -> (params, opt_state, metrics)``.

    ``grad_transform`` hooks the beyond-paper compressed/ring allreduce in
    (identity under plain pjit where XLA inserts the psum itself).
    """

    def train_step(params, opt_state: OptState, batch):
        (total, parts), grads = jax.value_and_grad(
            lambda p: loss_fn(model, p, batch), has_aux=True
        )(params)
        if grad_transform is not None:
            grads = grad_transform(grads)
        with jax.named_scope("optimizer"):
            lr = lr_schedule(opt_state.step)
            opt_state, params = optimizer.update(grads, opt_state, params, lr)
            # NOTE: elementwise square + sum, NOT vdot — vdot reshapes each
            # leaf to 1-D, which GSPMD can only partition by all-gathering
            # the whole (f32-upcast) tensor; measured at +4.5 GB/layer on
            # qwen3-moe.
            gnorm = jnp.sqrt(
                sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                    for g in jax.tree_util.tree_leaves(grads))
            )
        metrics = dict(parts, total=total, lr=lr, grad_norm=gnorm)
        return params, opt_state, metrics

    return train_step


def make_partial_grad_step(model: Model) -> Callable:
    """The per-host half of cluster (hostsync) training.

    Returns ``grad_step(params, batch) -> (grads, sums)`` computing this
    host's UNNORMALIZED contribution to the global objective over its local
    rows only:

        F_p(params) = Σ_p mask·ce  +  router_aux_weight · den_p · aux_p
        sums        = {num: Σ mask·ce, den: Σ mask, auxden: den_p · aux_p}

    The global masked-mean step is ``total = (Σ_p F_p) / max(Σ_p den_p, 1)``
    — a ratio of ACROSS-host sums — so summing each host's ``grads`` and
    ``sums`` and applying :func:`make_apply_step` reproduces the
    single-program :func:`make_train_step` exactly (dense models; an MoE
    router aux becomes its den-weighted mean, which coincides for P=1).
    This is how a backend that cannot run cross-process XLA programs
    (CPU jaxlib — see :func:`repro.compat.multiprocess_compute_supported`)
    still trains one exact global model: partial gradients meet at the
    coordinator, the paper's host-aggregation topology.
    """

    def objective(params, batch):
        kwargs = {
            k: batch[k] for k in ("frames", "patch_embeds") if k in batch
        }
        logits, aux = model.forward(params, batch["tokens"], **kwargs)
        labels = batch["labels"]
        mask = batch["loss_mask"]
        if logits.shape[1] != labels.shape[1]:
            logits = logits[:, -labels.shape[1]:]
        ce = cross_entropy(logits, labels)
        num = jnp.sum(ce * mask)
        den = jnp.sum(mask)
        auxden = den * aux
        return num + model.cfg.router_aux_weight * auxden, {
            "num": num, "den": den, "auxden": auxden,
        }

    def grad_step(params, batch):
        (_, sums), grads = jax.value_and_grad(
            objective, has_aux=True
        )(params, batch)
        return grads, sums

    return grad_step


def make_apply_step(
    optimizer: Optimizer,
    lr_schedule: Callable[[jax.Array], jax.Array],
    *,
    aux_weight: float = 0.01,
) -> Callable:
    """The update half of cluster (hostsync) training.

    ``apply_step(params, opt_state, grads, sums) -> (params, opt_state,
    metrics)`` consumes the ACROSS-host sums of :func:`make_partial_grad_step`
    outputs.  Every host applies the identical update to its identical
    params — replicas stay bit-synchronized without a broadcast, and the
    metrics match :func:`make_train_step`'s.
    """

    def apply_step(params, opt_state: OptState, grads, sums):
        den = jnp.maximum(sums["den"], 1.0)
        loss = sums["num"] / den
        aux = sums["auxden"] / den
        grads = jax.tree_util.tree_map(lambda g: g / den, grads)
        lr = lr_schedule(opt_state.step)
        opt_state, params = optimizer.update(grads, opt_state, params, lr)
        gnorm = jnp.sqrt(
            sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                for g in jax.tree_util.tree_leaves(grads))
        )
        metrics = {
            "loss": loss,
            "aux": aux,
            "total": loss + aux_weight * aux,
            "lr": lr,
            "grad_norm": gnorm,
            "tokens": sums["den"],
        }
        return params, opt_state, metrics

    return apply_step


def plan_buckets(params_abs: PyTree, n_buckets: int) -> Tuple[Tuple[int, ...], ...]:
    """Split the param-leaf list into contiguous, byte-balanced groups.

    Buckets are the cluster transport's unit of pipelining: the hostsync
    grad step emits one flat f32 vector per group, so bucket *i*'s
    reduction overlaps bucket *i+1*'s encode.  Greedy contiguous packing —
    leaf order (and therefore the vector layout) is the deterministic
    ``tree_leaves`` order every worker shares.
    """
    leaves = jax.tree_util.tree_leaves(params_abs)
    n_leaves = len(leaves)
    n_buckets = max(1, min(int(n_buckets), n_leaves))
    sizes = [
        int(jnp.dtype(l.dtype).itemsize)
        * (int(math.prod(l.shape)) if l.shape else 1)
        for l in leaves
    ]
    groups = []
    start = 0
    left_bytes = float(sum(sizes))
    for b in range(n_buckets):
        buckets_left = n_buckets - b
        if buckets_left == 1:
            groups.append(tuple(range(start, n_leaves)))
            break
        target = left_bytes / buckets_left
        take, acc = 1, sizes[start]
        while (
            start + take < n_leaves
            and (n_leaves - start - take) > (buckets_left - 1)
            and abs(acc + sizes[start + take] - target) <= abs(acc - target)
        ):
            acc += sizes[start + take]
            take += 1
        groups.append(tuple(range(start, start + take)))
        start += take
        left_bytes -= acc
    return tuple(groups)


def make_bucketed_grad_step(
    model: Model,
    bucket_groups: Tuple[Tuple[int, ...], ...],
) -> Callable:
    """:func:`make_partial_grad_step` with the grad pytree flattened into
    one f32 vector per bucket group — the cluster transport's wire format.
    Returns ``grad_step(params, batch) -> (bucket_vecs, sums)``.
    """
    base = make_partial_grad_step(model)

    def grad_step(params, batch):
        grads, sums = base(params, batch)
        leaves = jax.tree_util.tree_leaves(grads)
        vecs = tuple(
            leaves[grp[0]].astype(jnp.float32).reshape(-1)
            if len(grp) == 1 else
            jnp.concatenate(
                [leaves[i].astype(jnp.float32).reshape(-1) for i in grp]
            )
            for grp in bucket_groups
        )
        return vecs, sums

    return grad_step


def make_bucketed_apply_step(
    optimizer: Optimizer,
    lr_schedule: Callable[[jax.Array], jax.Array],
    params_abs: PyTree,
    bucket_groups: Tuple[Tuple[int, ...], ...],
    *,
    aux_weight: float = 0.01,
) -> Callable:
    """:func:`make_apply_step` taking the reduced bucket vectors instead of
    a grad pytree; the unflatten happens inside the jitted step.  Exact
    inverse of :func:`make_bucketed_grad_step`'s flatten (f32 round-trip of
    f32/bf16 grads is lossless), so bucketing never changes numerics.
    """
    base = make_apply_step(optimizer, lr_schedule, aux_weight=aux_weight)
    leaves_abs, treedef = jax.tree_util.tree_flatten(params_abs)
    shapes = [l.shape for l in leaves_abs]
    dtypes = [l.dtype for l in leaves_abs]
    counts = [int(math.prod(s)) if s else 1 for s in shapes]

    def apply_step(params, opt_state: OptState, bucket_vecs, sums):
        leaves = [None] * len(leaves_abs)
        for grp, vec in zip(bucket_groups, bucket_vecs):
            off = 0
            for i in grp:
                n = counts[i]
                leaves[i] = (
                    vec[off:off + n].reshape(shapes[i]).astype(dtypes[i])
                )
                off += n
        grads = jax.tree_util.tree_unflatten(treedef, leaves)
        return base(params, opt_state, grads, sums)

    return apply_step


def residual_bytes(model: Model, batch_abs: Dict[str, Any]) -> int:
    """Bytes of saved-for-backward residuals of one loss VJP (no allocation).

    ``jax.vjp``'s pullback is a Partial pytree whose leaves ARE the residual
    arrays, so ``eval_shape`` of it prices the backward pass's live memory —
    the footprint ``train_precision="int8-fused"`` shrinks by saving K/V and
    scan activations as int8 + per-row scales instead of full-width floats.
    """
    params_abs, _ = model.init_params(abstract=True)

    def f(params, batch):
        _, pullback = jax.vjp(
            lambda p: loss_fn(model, p, batch)[0],
            params,
        )
        return pullback

    pb = jax.eval_shape(f, params_abs, batch_abs)
    return int(sum(
        jnp.dtype(l.dtype).itemsize * (int(math.prod(l.shape)) if l.shape else 1)
        for l in jax.tree_util.tree_leaves(pb)
    ))


def make_eval_step(model: Model) -> Callable:
    def eval_step(params, batch):
        _, parts = loss_fn(model, params, batch)
        return parts

    return eval_step


def make_serve_step(model: Model) -> Callable:
    """One-token decode: (params, token, cache, pos) -> (next_token, logits, cache)."""

    def serve_step(params, token, cache, pos):
        logits, cache = model.decode_step(params, token, cache, pos)
        next_token = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
        return next_token[:, None], logits, cache

    return serve_step


def make_prefill_step(model: Model, cache_len: int) -> Callable:
    def prefill_step(params, tokens, **kwargs):
        return model.prefill(params, tokens, cache_len, **kwargs)

    return prefill_step


# ---------------------------------------------------------------------------
# Abstract train state + the ShardingPlan builder
# ---------------------------------------------------------------------------


def abstract_opt_state(optimizer: Optimizer, params: PyTree) -> OptState:
    """Optimizer state as ShapeDtypeStructs — ``eval_shape`` of the real
    ``init``, so any optimizer (SGD's ``nu=None``, AdamW's two moments)
    yields the exact state structure without allocating a byte."""
    return jax.eval_shape(optimizer.init, params)


def abstract_train_state(
    model: Model, optimizer: Optimizer
) -> Tuple[PyTree, PyTree, OptState]:
    """(params, logical_axes, opt_state) as abstract trees (no allocation)."""
    params, axes = model.init_params(abstract=True)
    return params, axes, abstract_opt_state(optimizer, params)


def abstract_batch(global_rows: int, seq_len: int) -> Dict[str, Any]:
    """The Stannis batch as ShapeDtypeStructs (keys match ``BATCH_AXES``)."""
    SDS = jax.ShapeDtypeStruct
    return {
        "tokens": SDS((global_rows, seq_len), jnp.int32),
        "labels": SDS((global_rows, seq_len), jnp.int32),
        "loss_mask": SDS((global_rows, seq_len), jnp.float32),
    }


def build_sharding_plan(
    model: Model,
    optimizer: Optimizer,
    *,
    mesh: Mesh,
    global_rows: int,
    seq_len: int,
    extra_rules: Optional[Dict[str, Any]] = None,
) -> ShardingPlan:
    """Resolve the rule table against ``mesh`` into one ShardingPlan.

    Size-aware (via :func:`arg_shardings_for_tree`): a dim a mesh axis does
    not divide falls back to replicated on that dim, so the plan is valid as
    jit ARGUMENT shardings on any mesh shape.  Optimizer moments reuse the
    parameter shardings (same shapes, f32), the step counter and metrics are
    replicated, and batch rows shard over the dp axes.
    """
    rules = make_rules(
        fsdp=bool(getattr(model.cfg, "fsdp", False)), extra=extra_rules or None
    )
    rules.setdefault("seq_data", None)
    replicated = NamedSharding(mesh, P())

    params_abs, p_axes = model.init_params(abstract=True)
    p_sh = arg_shardings_for_tree(p_axes, params_abs, rules, mesh)
    opt_abs = abstract_opt_state(optimizer, params_abs)
    opt_sh = OptState(
        step=replicated,
        mu=p_sh,
        nu=None if opt_abs.nu is None else p_sh,
    )
    batch_abs = abstract_batch(global_rows, seq_len)
    b_sh = arg_shardings_for_tree(BATCH_AXES, batch_abs, rules, mesh)

    data_axis = int(mesh.shape.get("data", 1)) if "data" in mesh.axis_names else 1
    return ShardingPlan(
        mesh=mesh,
        rules=rules,
        params=p_sh,
        opt=opt_sh,
        batch=b_sh,
        replicated=replicated,
        global_rows=int(global_rows),
        data_axis=data_axis,
    )
