"""Mixture-of-Experts decoder LM (dbrx-132b: 16e top-4; qwen3-moe-30b-a3b: 128e top-8).

Two expert layers, chosen by the config.  Dropless (``capacity_factor``
None, qwen3-moe): the router spans all ``n_experts``, this chip holds
``experts_held`` of them, and their token copies run through one grouped
matmul with per-expert row counts (:func:`_moe_mlp_grouped`).  Capacity
(dbrx): expert weights carry the ``experts`` logical axis which the sharding
rules map onto the ``model`` mesh axis, and token dispatch uses the
sort-by-expert + capacity layout (MaxText/GShard style, but with
gather/scatter instead of one-hot einsum so memory is O(E·C·d) not
O(T·E·C)); under pjit the scatter from token-sharded activations into
expert-sharded buffers lowers to an all-to-all.
"""
from __future__ import annotations

from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.distributed.sharding import with_logical_constraint as wlc
from repro.models import layers as L
from repro.models.config import ModelConfig
from repro.models.param import ParamBuilder, build, scaled_init, stacked

PyTree = Any


# ---------------------------------------------------------------------------
# MoE layer
# ---------------------------------------------------------------------------


def init_moe_mlp(b, name: str, d_model: int, d_ff: int, n_experts: int,
                 held: int):
    """The router over all ``n_experts``; SwiGLU weights of the ``held``
    experts."""
    s = b.scope(name)
    s.param("router", (d_model, n_experts), ("embed", "experts"), init=scaled_init(0))
    s.param("wi_gate", (held, d_model, d_ff),
            ("experts", "embed", "expert_mlp"), init=scaled_init(-2))
    s.param("wi_up", (held, d_model, d_ff),
            ("experts", "embed", "expert_mlp"), init=scaled_init(-2))
    s.param("wo", (held, d_ff, d_model),
            ("experts", "expert_mlp", "embed"), init=scaled_init(-2))


def expert_capacity(n_tokens: int, n_experts: int, k: int, capacity_factor: float) -> int:
    c = int(n_tokens * k * capacity_factor / n_experts)
    return max(8, ((c + 127) // 128) * 128)  # MXU-aligned


# Dispatch implementation: "auto" picks the shard_map group-local path when a
# mesh with a >1 "model" axis is active (the production EP path), else the
# fused Pallas dispatch+expert-GEMM kernel when cfg.fused_moe; "fused" /
# "dense" force the single-program fused-kernel / gather-scatter paths (the
# perf A/B baselines).  Env REPRO_MOE_IMPL overrides.
import os as _os

MOE_IMPL = _os.environ.get("REPRO_MOE_IMPL", "auto")


def moe_mlp(
    p: Dict, x: jax.Array, cfg: ModelConfig
) -> Tuple[jax.Array, jax.Array, Optional[jax.Array]]:
    """x: (B, S, d) -> (out, aux_loss, rows).

    Dropless configs take :func:`_moe_mlp_grouped`, whose ``rows`` counts
    the layer's expert rows; capacity configs dispatch on MOE_IMPL and give
    ``rows`` None."""
    if cfg.capacity_factor is None:
        return _moe_mlp_grouped(p, x, cfg)
    if cfg.resolved_experts_held() != cfg.n_experts:
        raise ValueError("a share of the experts needs dropless routing "
                         "(capacity_factor None)")
    return (*_moe_mlp_capacity(p, x, cfg), None)


def _moe_mlp_capacity(p: Dict, x: jax.Array, cfg: ModelConfig) -> Tuple[jax.Array, jax.Array]:
    impl = MOE_IMPL
    if impl == "auto":
        from repro.compat import get_abstract_mesh

        mesh = get_abstract_mesh()
        if (
            mesh is not None
            and "model" in mesh.axis_names and mesh.shape["model"] > 1
            and cfg.n_experts % mesh.shape["model"] == 0
        ):
            return _moe_mlp_local(p, x, cfg, mesh)
        impl = "fused" if cfg.fused_moe else "dense"
    if impl == "fused":
        return _moe_mlp_fused(p, x, cfg)
    return _moe_mlp_dense(p, x, cfg)


def _tile_rows(group_sizes: jax.Array, tm: int) -> jax.Array:
    """Rows the grouped matmul's row tiles of ``tm`` cover: a group runs
    every tile from the one holding its first row to the one holding its
    last, so a tile shared by two groups is run (and counted) twice."""
    ends = jnp.cumsum(group_sizes)
    starts = ends - group_sizes
    tiles = (ends + tm - 1) // tm - starts // tm
    return jnp.sum(jnp.where(group_sizes > 0, tiles, 0)) * tm


# The dispatch into the sorted buffer and the combine out of it are row
# gathers whose transposes are gathers too (``order`` and ``inv`` are inverse
# permutations of the token copies), so neither backward pass needs a
# scatter-add.


@partial(jax.custom_vjp, nondiff_argnums=(3,))
def _dispatch(xf: jax.Array, order: jax.Array, inv: jax.Array, k: int) -> jax.Array:
    """Buffer row i holds token ``order[i] // k``: (M, d) from xf (T, d)."""
    return xf[order // k]


def _dispatch_fwd(xf, order, inv, k):
    return _dispatch(xf, order, inv, k), inv


def _dispatch_bwd(k, inv, dxs):
    # token t gets the rows of its k copies that are in the buffer
    M = dxs.shape[0]
    row = inv.reshape(-1, k)
    parts = dxs[jnp.minimum(row, M - 1)].astype(jnp.float32)
    dxf = jnp.sum(jnp.where((row < M)[..., None], parts, 0.0), axis=1)
    return dxf.astype(dxs.dtype), None, None


_dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@jax.custom_vjp
def _combine(ys: jax.Array, w: jax.Array, inv: jax.Array, order: jax.Array) -> jax.Array:
    """Token t's output: sum over its copies j of w[t, j] x buffer row
    ``inv[t*k + j]`` (a copy past the buffer has w 0).  ys (M, d), w (T, k)."""
    T, k = w.shape
    rows = ys[jnp.minimum(inv, ys.shape[0] - 1).reshape(T, k)]
    return jnp.sum(rows.astype(jnp.float32) * w[..., None], axis=1).astype(ys.dtype)


def _combine_fwd(ys, w, inv, order):
    return _combine(ys, w, inv, order), (ys, w, inv, order)


def _combine_bwd(res, dout):
    ys, w, inv, order = res
    T, k = w.shape
    g = dout.astype(jnp.float32)
    # gathered in dout's dtype, widened after: the same numbers, and no
    # (M, d) f32 copy
    dys = dout[order // k].astype(jnp.float32) * w.reshape(-1)[order][:, None]
    rows = ys[jnp.minimum(inv, ys.shape[0] - 1).reshape(T, k)]
    dw = jnp.einsum("tkd,td->tk", rows.astype(jnp.float32), g)
    return dys.astype(ys.dtype), dw, None, None


_combine.defvjp(_combine_fwd, _combine_bwd)


def _experts(xs: jax.Array, w_gu: jax.Array, wo: jax.Array,
             group_sizes: jax.Array) -> jax.Array:
    """Gate and up in one grouped matmul, SwiGLU, then down: (rows, d)."""
    from repro.kernels import ops as kops

    with jax.named_scope("moe_experts"):
        g, u = jnp.split(kops.grouped_matmul(xs, w_gu, group_sizes), 2,
                         axis=-1)
        return kops.grouped_matmul(jax.nn.silu(g) * u, wo, group_sizes)


def _full_buffer(xf, w, order, group_sizes, w_gu, wo):
    """The layer's result over a buffer of every row that could arrive,
    T·min(k, held): xf (T, d), w (T, k) the gates (0 for copies held
    elsewhere), ``order`` the copies sorted by expert."""
    T, k = w.shape
    M = T * min(k, group_sizes.shape[0])
    with jax.named_scope("moe_dispatch"):
        inv = jnp.argsort(order)                                # copy -> row
        xs = _dispatch(xf, order[:M], inv, k)                   # (M, d)
    ys = _experts(xs, w_gu, wo, group_sizes)
    with jax.named_scope("moe_combine"):
        return _combine(ys, w, inv, order[:M])


def _compact(xf, w, order, group_sizes, w_gu, wo, C):
    """The same result over the first C rows of the sorted copies, exact
    when every routed copy is among them: buffer row i is token
    ``order[i] // k`` with gate ``w.flat[order[i]]``, and the combine adds
    each row into its token in f32."""
    T, k = w.shape
    with jax.named_scope("moe_dispatch"):
        tok = order[:C] // k
        xs = xf[tok]                                            # (C, d)
    ys = _experts(xs, w_gu, wo, group_sizes)
    with jax.named_scope("moe_combine"):
        wr = w.reshape(-1)[order[:C]]
        out = jnp.zeros(xf.shape, jnp.float32).at[tok].add(
            ys.astype(jnp.float32) * wr[:, None])
        return out.astype(ys.dtype)


def _compact_grads(xf, w, order, group_sizes, w_gu, wo, dout, C):
    """:func:`_compact`'s gradients for (xf, w, w_gu, wo): C-row gathers
    of the token rows, C-row scatter-adds back, f32 where the forward
    accumulates."""
    T, k = w.shape
    with jax.named_scope("moe_dispatch"):
        tok = order[:C] // k
        xs = xf[tok]
    ys, experts_vjp = jax.vjp(
        lambda xs, a, b: _experts(xs, a, b, group_sizes), xs, w_gu, wo)
    with jax.named_scope("moe_combine"):
        wr = w.reshape(-1)[order[:C]]
        g = dout[tok].astype(jnp.float32)
        dwr = jnp.sum(ys.astype(jnp.float32) * g, axis=-1)
        dw = jnp.zeros(T * k, dwr.dtype).at[order[:C]].set(dwr)
        dys = (g * wr[:, None]).astype(ys.dtype)
    dxs, dgu, dwo = experts_vjp(dys)
    with jax.named_scope("moe_dispatch"):
        dxf = jnp.zeros(xf.shape, jnp.float32).at[tok].add(
            dxs.astype(jnp.float32))
    return dxf.astype(xf.dtype), dw.reshape(T, k), dgu, dwo


def _full_grads(xf, w, order, group_sizes, w_gu, wo, dout):
    """:func:`_full_buffer`'s gradients for (xf, w, w_gu, wo)."""
    _, vjp = jax.vjp(
        lambda xf, w, a, b: _full_buffer(xf, w, order, group_sizes, a, b),
        xf, w, w_gu, wo)
    return vjp(dout)


# Under the layer's checkpoint, differentiating a ``cond`` would save the
# union of both branches' residuals (the full buffer's zero-filled when the
# compact branch runs); this vjp saves only the inputs, and each branch of
# its backward recomputes its own forward.


@partial(jax.custom_vjp, nondiff_argnums=(6,))
def _routed_rows(xf, w, order, group_sizes, w_gu, wo, C):
    """:func:`_compact` when the copies routed here fit its C rows, else
    :func:`_full_buffer`; chosen on the device."""
    return jax.lax.cond(jnp.sum(group_sizes) <= C, partial(_compact, C=C),
                        _full_buffer, xf, w, order, group_sizes, w_gu, wo)


def _routed_rows_fwd(xf, w, order, group_sizes, w_gu, wo, C):
    res = (xf, w, order, group_sizes, w_gu, wo)
    return _routed_rows(*res, C), res


def _routed_rows_bwd(C, res, dout):
    group_sizes = res[3]
    dxf, dw, dgu, dwo = jax.lax.cond(
        jnp.sum(group_sizes) <= C, partial(_compact_grads, C=C), _full_grads,
        *res, dout)
    return dxf, dw, None, None, dgu, dwo


_routed_rows.defvjp(_routed_rows_fwd, _routed_rows_bwd)


def compact_rows(n_tokens: int, k: int, held: int, n_experts: int) -> int:
    """Rows of the compact buffer: twice the copies a uniform router sends
    to ``held`` of ``n_experts``, in whole grouped-matmul tiles, at most
    the full buffer's T·min(k, held)."""
    from repro.kernels.ops import GMM_TILE_M

    want = -(-2 * n_tokens * k * held // n_experts)
    return min(n_tokens * min(k, held), -(-want // GMM_TILE_M) * GMM_TILE_M)


def _moe_mlp_grouped(
    p: Dict, x: jax.Array, cfg: ModelConfig
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Dropless routing over all ``n_experts``; this chip computes its held
    experts' part of the result.

    The router's outputs stay in f32: softmax, top-k, and the k gates
    renormalised.  Token copies routed to a held expert are sorted by
    expert; one grouped matmul with per-expert row counts runs gate and up,
    then SwiGLU, then one runs down, and the gated combine adds each token's
    copies back.  The rows run through a compact buffer of
    :func:`compact_rows` C rows when the copies routed here fit it, else
    (decided on the device) through the static buffer of T·min(k, held)
    rows, the most that can arrive; where C reaches that, the static
    buffer alone.  Copies routed to experts held elsewhere are not computed
    here (on one chip the layer runs without its exchange); none is dropped.
    The Switch aux loss, E·Σ_e (count_e/T)·p_e, spans all E router outputs.

    Returns (out, aux, rows): ``rows`` int32[3] is the copies routed to held
    experts, the rows the grouped matmul's tiles cover, and 1 if the layer
    took the static buffer because the copies did not fit C rows, else 0.
    """
    from repro.kernels import ops as kops

    B, S, d = x.shape
    T = B * S
    E, k = cfg.n_experts, cfg.experts_per_token
    held = cfg.resolved_experts_held()
    xf = x.reshape(T, d)
    with jax.named_scope("moe_router"):
        logits = xf.astype(jnp.float32) @ p["router"].astype(jnp.float32)
        probs = jax.nn.softmax(logits, axis=-1)                 # (T, E)
        gate, expert = jax.lax.top_k(probs, k)                  # (T, k)
        gate = gate / jnp.sum(gate, axis=-1, keepdims=True)
        count = jnp.bincount(expert.reshape(-1), length=E)
        aux = E * jnp.sum(count / T * jnp.mean(probs, axis=0))
    with jax.named_scope("moe_dispatch"):
        local = expert.reshape(-1) - cfg.first_expert           # (T*k,)
        is_held = (local >= 0) & (local < held)
        key = jnp.where(is_held, local, held)                   # elsewhere: last
        order = jnp.argsort(key, stable=True)                   # copies by expert
        group_sizes = jnp.bincount(key, length=held + 1)[:held].astype(jnp.int32)
    with jax.named_scope("moe_experts"):
        w_gu = jnp.concatenate([p["wi_gate"], p["wi_up"]], axis=-1)
    with jax.named_scope("moe_combine"):
        w = jnp.where(is_held.reshape(T, k), gate, 0.0)
    args = (xf, w, order, group_sizes, w_gu.astype(x.dtype),
            p["wo"].astype(x.dtype))
    C = compact_rows(T, k, held, E)
    if C < T * min(k, held):
        out = _routed_rows(*args, C)
    else:
        out = _full_buffer(*args)
    out = wlc(out.reshape(B, S, d), "batch", "seq", "act_embed")
    routed = jnp.sum(group_sizes)
    rows = jnp.stack([routed, _tile_rows(group_sizes, kops.GMM_TILE_M),
                      (routed > C).astype(routed.dtype)])
    return out, aux.astype(jnp.float32), rows.astype(jnp.int32)


def _moe_mlp_fused(p: Dict, x: jax.Array, cfg: ModelConfig) -> Tuple[jax.Array, jax.Array]:
    """Single-program path through the fused Pallas kernel.

    Same routing/capacity math as :func:`_moe_mlp_dense` (parity-tested, incl.
    capacity overflow), but the dispatch gather, capacity masking, expert
    SwiGLU, and gate scaling run in one kernel — the (T·k, d) token-copy
    tensor and the g/u/h intermediates never round-trip HBM.  Backward
    recomputes through the ref oracle (see kernels/ops.py).
    """
    from repro.kernels import ops as kops

    B, S, d = x.shape
    T = B * S
    C = expert_capacity(T, cfg.n_experts, cfg.experts_per_token, cfg.capacity_factor)
    out, aux = kops.fused_moe_mlp(
        x.reshape(T, d), p["router"], p["wi_gate"], p["wi_up"], p["wo"],
        k=cfg.experts_per_token, capacity=C,
        interpret=kops.interpret_default(),
    )
    out = wlc(out.reshape(B, S, d), "batch", "seq", "act_embed")
    return out, aux


def _moe_mlp_local(
    p: Dict, x: jax.Array, cfg: ModelConfig, mesh
) -> Tuple[jax.Array, jax.Array]:
    """Group-local EP dispatch (GShard grouped capacity), zero all-to-all.

    Layout: token groups = dp shards (("pod","data") slices of the batch);
    experts sharded over "model".  Device (g, j) routes ITS tokens to ITS
    experts only, with per-group capacity C/n_groups — dispatch gather and
    combine scatter are LOCAL.  Each expert's shards across j see disjoint
    token groups, so expert compute is pure data parallelism; the only
    communication is the combine psum of (T_loc, d) over "model" — the same
    collective a dense TP MLP needs anyway.

    vs the GSPMD-auto dense path: the compiler partitions the global
    gather/scatter by REPLICATING the (T·k, d) token-copy tensor per device
    (~69 GB f32 for qwen3-30b at 4k·256) and all-reducing it; this path
    removes those entirely.
    """
    from repro.distributed.sharding import get_rules

    E, k = cfg.n_experts, cfg.experts_per_token
    dp_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    n_groups = 1
    for a in dp_axes:
        n_groups *= mesh.shape[a]
    n_model = mesh.shape["model"]
    E_loc = E // n_model
    B, S, d = x.shape
    T = B * S
    C_group = expert_capacity(T // max(1, n_groups), E, k, cfg.capacity_factor)

    # DP-attention layout: batch rows also sharded over "model".  The group's
    # tokens are reconstituted with an EXPLICIT tiled all-gather (and the
    # combined output returned with a psum_scatter) — letting GSPMD reshard
    # instead triggers involuntary full rematerialization (replicate+slice).
    batch_rule = get_rules().get("batch")
    rule_axes = (batch_rule,) if isinstance(batch_rule, str) else tuple(batch_rule or ())
    over_model = "model" in rule_axes and (B % (n_groups * n_model) == 0)

    from jax.experimental.shard_map import shard_map
    from jax.sharding import PartitionSpec as P

    def local_fn(router, wg, wu, wo, xl):
        # xl: (B_loc, S, d); wg/wu/wo: (E_loc, ...); router replicated
        if over_model:
            xl = jax.lax.all_gather(xl, "model", axis=0, tiled=True)
        Bl = xl.shape[0]
        Tl = Bl * S
        xf = xl.reshape(Tl, d)
        logits = (xf @ router.astype(jnp.float32)).astype(jnp.float32)
        probs = jax.nn.softmax(logits, axis=-1)                 # (Tl, E)
        gate_vals, expert_ids = jax.lax.top_k(probs, k)
        gate_vals = gate_vals / jnp.sum(gate_vals, axis=-1, keepdims=True)

        # Switch aux from GLOBAL stats: psum the (E,) vectors over dp.
        # bincount, not one_hot: the (Tl, k, E) one-hot costs 268 MB of HBM
        # traffic per layer at qwen3 dims; the bincount is (Tl*k) ints.
        tok_frac = (
            jnp.bincount(expert_ids.reshape(-1), length=E).astype(jnp.float32)
            / expert_ids.shape[0]
        )
        prob_frac = jnp.mean(probs, axis=0)
        if dp_axes:
            tok_frac = jax.lax.pmean(tok_frac, dp_axes)
            prob_frac = jax.lax.pmean(prob_frac, dp_axes)
        aux = E * jnp.sum(tok_frac * prob_frac)

        # local experts on this model shard
        e0 = jax.lax.axis_index("model") * E_loc
        flat_expert = expert_ids.reshape(-1)                    # (Tl*k,)
        flat_token = jnp.repeat(jnp.arange(Tl), k)
        flat_gate = gate_vals.reshape(-1)
        local_e = flat_expert - e0                              # in [0, E_loc)?
        is_local = (local_e >= 0) & (local_e < E_loc)

        order = jnp.argsort(jnp.where(is_local, local_e, E_loc), stable=True)
        se = local_e[order]
        st = flat_token[order]
        sg = flat_gate[order]
        sl = is_local[order]

        counts = jnp.bincount(jnp.where(is_local, local_e, E_loc),
                              length=E_loc + 1)[:E_loc]
        offsets = jnp.concatenate(
            [jnp.zeros(1, counts.dtype), jnp.cumsum(counts)[:-1]]
        )
        pos_in_e = jnp.arange(Tl * k) - offsets[jnp.clip(se, 0, E_loc - 1)]
        keep = sl & (pos_in_e < C_group)
        slot = jnp.where(keep, se * C_group + pos_in_e, E_loc * C_group)

        # compact dispatch: scatter token INDICES (ints) into slots, then
        # gather exactly (E_loc*C, d) rows — materializing xf[st] first would
        # move the full (Tl*k, d) copy tensor (~12x larger than the buffer)
        slot_tok = jnp.zeros((E_loc * C_group + 1,), jnp.int32).at[slot].set(
            st.astype(jnp.int32))
        slot_ok = jnp.zeros((E_loc * C_group + 1,), jnp.bool_).at[slot].set(keep)
        buf = xf[slot_tok[: E_loc * C_group]]
        buf = buf * slot_ok[: E_loc * C_group, None].astype(buf.dtype)
        buf = buf.reshape(E_loc, C_group, d)

        g = jnp.einsum("ecd,edf->ecf", buf, wg.astype(buf.dtype))
        u = jnp.einsum("ecd,edf->ecf", buf, wu.astype(buf.dtype))
        h = jax.nn.silu(g) * u
        y = jnp.einsum("ecf,efd->ecd", h, wo.astype(buf.dtype))

        yf = y.reshape(E_loc * C_group, d)
        safe_slot = jnp.minimum(slot, E_loc * C_group - 1)
        out_copies = yf[safe_slot] * (sg * keep)[:, None].astype(yf.dtype)
        out = jnp.zeros((Tl, d), yf.dtype).at[st].add(out_copies)
        out = out.reshape(Bl, S, d)
        # combine partial expert outputs across the model axis; in the
        # DP-attention layout fuse the combine with the re-scatter (RS costs
        # half an AR and lands directly in the 256-way layout)
        if over_model:
            out = jax.lax.psum_scatter(out, "model", scatter_dimension=0,
                                       tiled=True)
        else:
            out = jax.lax.psum(out, "model")
        return out, aux.astype(jnp.float32)

    if over_model:
        batch_spec = (*dp_axes, "model")
    else:
        batch_spec = dp_axes if len(dp_axes) > 1 else (dp_axes[0] if dp_axes else None)
    out, aux = shard_map(
        local_fn,
        mesh=mesh,
        in_specs=(
            P(), P("model"), P("model"), P("model"),
            P(batch_spec, None, None),
        ),
        out_specs=(P(batch_spec, None, None), P()),
        check_rep=False,
    )(p["router"], p["wi_gate"], p["wi_up"], p["wo"], x)
    out = wlc(out, "batch", "seq", "act_embed")
    return out, aux


def _moe_mlp_dense(p: Dict, x: jax.Array, cfg: ModelConfig) -> Tuple[jax.Array, jax.Array]:
    """Single-program gather/scatter dispatch (GSPMD-auto partitioning).

    Top-k routing with normalized gates; load-balancing aux loss (Switch-style):
    ``E * Σ_e f_e · p_e`` where f_e = token fraction, p_e = mean router prob.
    """
    B, S, d = x.shape
    E, k = cfg.n_experts, cfg.experts_per_token
    T = B * S
    C = expert_capacity(T, E, k, cfg.capacity_factor)
    xf = x.reshape(T, d)

    router_logits = (xf @ p["router"].astype(jnp.float32)).astype(jnp.float32)
    probs = jax.nn.softmax(router_logits, axis=-1)              # (T, E)
    gate_vals, expert_ids = jax.lax.top_k(probs, k)             # (T, k)
    gate_vals = gate_vals / jnp.sum(gate_vals, axis=-1, keepdims=True)

    # aux load-balance loss
    tok_frac = jnp.mean(
        jax.nn.one_hot(expert_ids, E, dtype=jnp.float32).sum(axis=1), axis=0
    )                                                           # (E,)
    prob_frac = jnp.mean(probs, axis=0)                         # (E,)
    aux = E * jnp.sum(tok_frac * prob_frac)

    # ---- dispatch: sort token-copies by expert, take first C per expert ----
    flat_expert = expert_ids.reshape(-1)                        # (T*k,)
    flat_token = jnp.repeat(jnp.arange(T), k)                   # (T*k,)
    flat_gate = gate_vals.reshape(-1)                           # (T*k,)

    order = jnp.argsort(flat_expert, stable=True)               # group by expert
    se = flat_expert[order]
    st = flat_token[order]
    sg = flat_gate[order]

    counts = jnp.bincount(flat_expert, length=E)                # (E,)
    offsets = jnp.concatenate([jnp.zeros(1, counts.dtype), jnp.cumsum(counts)[:-1]])
    pos_in_expert = jnp.arange(T * k) - offsets[se]
    keep = pos_in_expert < C
    slot = jnp.where(keep, se * C + pos_in_expert, E * C)       # overflow -> dump row

    # scatter tokens into expert buffers (E*C+1, d); final row is the dump
    gathered = xf[st] * keep[:, None].astype(xf.dtype)
    buf = jnp.zeros((E * C + 1, d), xf.dtype).at[slot].set(gathered)
    buf = buf[: E * C].reshape(E, C, d)
    buf = wlc(buf, "act_experts", None, None)

    # ---- expert compute (per-expert SwiGLU) ----
    g = jnp.einsum("ecd,edf->ecf", buf, p["wi_gate"].astype(buf.dtype))
    u = jnp.einsum("ecd,edf->ecf", buf, p["wi_up"].astype(buf.dtype))
    h = jax.nn.silu(g) * u
    h = wlc(h, "act_experts", None, None)
    y = jnp.einsum("ecf,efd->ecd", h, p["wo"].astype(buf.dtype))

    # ---- combine: gather back token copies, weight by gates, sum over k ----
    yf = y.reshape(E * C, d)
    safe_slot = jnp.minimum(slot, E * C - 1)
    out_copies = yf[safe_slot] * (sg * keep)[:, None].astype(yf.dtype)
    out = jnp.zeros((T, d), yf.dtype).at[st].add(out_copies)
    out = wlc(out.reshape(B, S, d), "batch", "seq", "act_embed")
    return out, aux.astype(jnp.float32)


# ---------------------------------------------------------------------------
# Model = dense skeleton with MoE FFN
# ---------------------------------------------------------------------------


def _init_block(s, cfg: ModelConfig):
    hd = cfg.resolved_head_dim()
    L.init_rmsnorm(s, "ln1", cfg.d_model)
    L.init_attention(
        s, "attn", cfg.d_model, cfg.n_heads, cfg.n_kv_heads, hd,
        qkv_bias=cfg.qkv_bias, qk_norm=cfg.qk_norm,
    )
    L.init_rmsnorm(s, "ln2", cfg.d_model)
    init_moe_mlp(s, "moe", cfg.d_model, cfg.d_ff, cfg.n_experts,
                 cfg.resolved_experts_held())


def init_params(cfg: ModelConfig, key=None, abstract=False, dtype=None):
    dtype = dtype or cfg.dtype

    def f(b: ParamBuilder):
        L.init_embedding(b, "embedding", cfg.vocab, cfg.d_model)
        _init_block(stacked(b, cfg.n_layers).scope("blocks"), cfg)
        L.init_rmsnorm(b, "ln_f", cfg.d_model)
        if not cfg.tie_embeddings:
            L.init_unembedding(b, "lm_head", cfg.vocab, cfg.d_model)

    return build(f, key=key, abstract=abstract, dtype=dtype)


def _block_train(lp, x, cfg: ModelConfig, positions):
    # the same scopes as dense.py's, so a device trace splits this step too
    with jax.named_scope("attention"):
        h = L.rms_norm(lp["ln1"], x)
        h = L.attention_train(
            lp["attn"], h, positions=positions, causal=True, window=cfg.window,
            rope_theta=cfg.rope_theta, precision=cfg.train_precision,
        )
        x = x + h
    with jax.named_scope("mlp"):
        h = L.rms_norm(lp["ln2"], x)
        y, aux, rows = moe_mlp(lp["moe"], h, cfg)
        return x + y, aux, rows


def forward(params, cfg: ModelConfig, tokens, **_):
    """-> (logits, total_aux_loss), plus ``{"moe_rows": int32[3]}`` (the
    grouped path's rows, summed over layers) when dropless."""
    with jax.named_scope("vocab"):
        x = L.embed(params["embedding"], tokens, cfg.dtype)
    positions = jnp.arange(x.shape[1])
    dropless = cfg.capacity_factor is None

    def body(lp, h):
        h, a, rows = _block_train(lp, h, cfg, positions)
        return h, a, (rows if dropless else jnp.zeros((3,), jnp.int32))

    fn = jax.checkpoint(body) if cfg.remat else body
    carry = (x, jnp.zeros((), jnp.float32), jnp.zeros((3,), jnp.int32))
    if cfg.scan_layers:
        def step(carry, lp):
            h, aux, rows = carry
            h, a, r = fn(lp, h)
            return (h, aux + a, rows + r), None

        with L.repeated_layers(cfg.n_layers):
            (x, aux, rows), _ = jax.lax.scan(step, carry, params["blocks"])
    else:
        x, aux, rows = carry
        for i in range(cfg.n_layers):
            lp = jax.tree_util.tree_map(lambda a: a[i], params["blocks"])
            x, a, r = fn(lp, x)
            aux, rows = aux + a, rows + r

    from repro.models.dense import _final

    logits = _final(params, x, cfg)
    if dropless:
        return logits, aux, {"moe_rows": rows}
    return logits, aux


def init_cache(cfg: ModelConfig, batch: int, cache_len: int, dtype=None):
    from repro.models import dense

    return dense.init_cache(cfg, batch, cache_len, dtype)


def cache_logical_axes(cfg: ModelConfig):
    from repro.models import dense

    return dense.cache_logical_axes(cfg)


def prefill(params, cfg: ModelConfig, tokens, cache_len: int, **_):
    x = L.embed(params["embedding"], tokens, cfg.dtype)
    positions = jnp.arange(x.shape[1])

    def body(lp, h):
        hn = L.rms_norm(lp["ln1"], h)
        attn_out, kv = L.attention_prefill(
            lp["attn"], hn, positions=positions, cache_len=cache_len,
            causal=True, window=cfg.window, rope_theta=cfg.rope_theta,
            kv_cache_dtype=cfg.kv_cache_dtype,
        )
        h = h + attn_out
        hn = L.rms_norm(lp["ln2"], h)
        y, _aux, _rows = moe_mlp(lp["moe"], hn, cfg)
        return h + y, kv

    fn = jax.checkpoint(body) if cfg.remat else body

    if cfg.scan_layers:
        x, cache = jax.lax.scan(lambda c, lp: fn(lp, c), x, params["blocks"])
    else:
        kvs = []
        for i in range(cfg.n_layers):
            lp = jax.tree_util.tree_map(lambda a: a[i], params["blocks"])
            x, kv = fn(lp, x)
            kvs.append(kv)
        cache = jax.tree_util.tree_map(lambda *ls: jnp.stack(ls), *kvs)
    from repro.models.dense import _final

    return _final(params, x[:, -1:], cfg), cache


def decode_step(params, cfg: ModelConfig, token, cache, pos):
    x = L.embed(params["embedding"], token, cfg.dtype)

    def body(h, xs):
        lp, kv = xs
        hn = L.rms_norm(lp["ln1"], h)
        attn_out, kv = L.attention_decode(
            lp["attn"], hn, kv, pos=pos, window=cfg.window, rope_theta=cfg.rope_theta
        )
        h = h + attn_out
        hn = L.rms_norm(lp["ln2"], h)
        y, _aux, _rows = moe_mlp(lp["moe"], hn, cfg)
        return h + y, kv

    from repro.models.dense import _final, _maybe_unrolled_scan

    x, new_cache = _maybe_unrolled_scan(cfg, body, x, (params["blocks"], cache))
    return _final(params, x, cfg), new_cache
