"""Fused MoE dispatch + expert-matmul as a single Pallas TPU kernel.

The unfused capacity-layout MoE (``models/moe.py::_moe_mlp_dense``) round-trips
five O(E·C·d)-to-O(E·C·f) tensors through HBM per layer: the gathered token
copies, the scattered dispatch buffer, and the g/u/h SwiGLU intermediates.
This kernel keeps all of them in VMEM:

  * **dispatch as a one-hot matmul** — each grid block (e, cb) owns ``bc``
    capacity slots of expert ``e``.  The slot→token table (built by
    :func:`moe_routing`, ordinary int ops) arrives as a ``(E·C, 1)`` int32
    operand; the block compares it against a token iota and multiplies the
    resulting selection matrix into the resident ``(T, d)`` activations on
    the MXU.  The gather never materializes in HBM, and empty slots (token
    index ``T``) select the zero row for free.
  * **capacity masking + combine scaling fused** — the per-slot gate (zero
    for empty slots, the normalized top-k weight otherwise) is applied to
    the expert output inside the kernel, so the only HBM write is the final
    gated ``(E·C, d)`` slot buffer.
  * **expert GEMMs** — wg/wu/wo blocks are index-mapped by the expert id,
    so each expert's weights are fetched once per ``C/bc`` blocks (Pallas
    revolving-buffer reuse) and the SwiGLU runs entirely in VMEM.

  * **combine as the transposed one-hot matmul** — the scatter-add of gated
    slot rows back to token rows is the dispatch selection matrix applied
    the other way: ``out[t] = Σ_s 1[slot_tok[s] = t] · y[s]``.  A second
    kernel (:func:`fused_moe_combine`) builds the same one-hot from the same
    ``(E·C, 1)`` slot table per token block and contracts it against the
    gated slot buffer on the MXU, so expert outputs never round-trip through
    an XLA scatter.  Each token row receives at most ``k`` nonzero addends
    (adding the 0 rows is exact in f32), which keeps the combine bit-exact
    vs the scatter-add (property-tested, including capacity-overflow drops).

What stays outside (in ordinary XLA, by necessity): the router matmul +
top-k + the stable sort that assigns capacity slots (Pallas TPU has no sort
primitive — vLLM's fused_moe splits the same way).  Those are O(T·k) index
ops, not the O(T·d·f) hot loop.

Scaling note: the dispatch kernel holds the full ``(T, d)`` activation block
in VMEM, so its scoped-VMEM limit is raised to :data:`_GEMM_VMEM_LIMIT`
(v5e has 128 MiB per core; 2048 tokens at d_model 2048 compile with 32
experts held).  A production kernel would double-buffer token tiles from
HBM.  The combine kernel streams the slot buffer in capacity blocks.  Tests
run in interpret mode; ``tests/test_chip_compile.py`` compiles both kernels
for a v5e at qwen3-moe-30b-a3b widths.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# the resident (T, d) tokens are double-buffered next to three expert
# weight blocks; the 16 MiB default cannot hold them at real widths
_GEMM_VMEM_LIMIT = 96 << 20
# slot rows per combine block (a divisor of the capacity)
_COMBINE_BLOCK_S = 512


def moe_routing(
    x: jax.Array,               # (T, d) tokens
    router: jax.Array,          # (d, E)
    k: int,
    capacity: int,
) -> Tuple[jax.Array, ...]:
    """Top-k routing + capacity-slot assignment (the sort stays in XLA).

    Returns ``(slot_tok, slot_gate, st, slot, keep, aux)``:
      * ``slot_tok``  (E·C, 1) int32 — token index per capacity slot, ``T``
        for empty slots (the kernel's one-hot then selects nothing);
      * ``slot_gate`` (E·C, 1) f32  — normalized gate per slot, 0 if empty;
      * ``st``/``slot``/``keep``    — the (T·k,) combine tables in dispatch
        order (token id, slot id with E·C as the drop sentinel, kept mask);
      * ``aux``                     — the Switch load-balance loss.
    """
    T, _ = x.shape
    E = router.shape[1]
    C = capacity

    logits = (x @ router.astype(jnp.float32)).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)                     # (T, E)
    gate_vals, expert_ids = jax.lax.top_k(probs, k)             # (T, k)
    gate_vals = gate_vals / jnp.sum(gate_vals, axis=-1, keepdims=True)

    tok_frac = jnp.mean(
        jax.nn.one_hot(expert_ids, E, dtype=jnp.float32).sum(axis=1), axis=0
    )
    prob_frac = jnp.mean(probs, axis=0)
    aux = E * jnp.sum(tok_frac * prob_frac)

    flat_expert = expert_ids.reshape(-1)                        # (T*k,)
    flat_token = jnp.repeat(jnp.arange(T), k)
    flat_gate = gate_vals.reshape(-1)

    order = jnp.argsort(flat_expert, stable=True)
    se = flat_expert[order]
    st = flat_token[order]
    sg = flat_gate[order]

    counts = jnp.bincount(flat_expert, length=E)
    offsets = jnp.concatenate([jnp.zeros(1, counts.dtype), jnp.cumsum(counts)[:-1]])
    pos_in_expert = jnp.arange(T * k) - offsets[se]
    keep = pos_in_expert < C
    slot = jnp.where(keep, se * C + pos_in_expert, E * C)

    # slot tables: empty slots keep the sentinel token index T / gate 0
    slot_tok = jnp.full((E * C + 1,), T, jnp.int32).at[slot].set(
        st.astype(jnp.int32))[: E * C]
    slot_gate = jnp.zeros((E * C + 1,), jnp.float32).at[slot].set(
        jnp.where(keep, sg, 0.0))[: E * C]
    return (slot_tok.reshape(-1, 1), slot_gate.reshape(-1, 1),
            st, slot, keep, aux.astype(jnp.float32))


def _fused_moe_kernel(
    tok_ref,                    # (bc, 1) int32 slot->token table block
    gate_ref,                   # (bc, 1) f32 slot gate block
    x_ref,                      # (T, d) resident tokens
    wg_ref, wu_ref, wo_ref,     # (1, d, f) / (1, d, f) / (1, f, d)
    y_ref,                      # (bc, d) gated expert output block
    *,
    bc: int,
    T: int,
):
    x = x_ref[...]                                              # (T, d)
    idx = tok_ref[...]                                          # (bc, 1)
    # dispatch gather as a one-hot matmul: sentinel index T matches no token;
    # a 0/1 selection in the tokens' own dtype copies rows exactly
    sel = (idx == jax.lax.broadcasted_iota(jnp.int32, (bc, T), 1)
           ).astype(x.dtype)
    xs = jax.lax.dot_general(                                   # (bc, d) MXU
        sel, x, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    # expert GEMMs in the weights' dtype with f32 accumulation
    wdt = wg_ref.dtype
    g = jax.lax.dot_general(xs.astype(wdt), wg_ref[0], (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    u = jax.lax.dot_general(xs.astype(wdt), wu_ref[0], (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    h = jax.nn.silu(g) * u
    y = jax.lax.dot_general(h.astype(wdt), wo_ref[0], (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    y_ref[...] = (y * gate_ref[...]).astype(y_ref.dtype)


def fused_moe_gemm(
    x: jax.Array,               # (T, d)
    wg: jax.Array,              # (E, d, f)
    wu: jax.Array,              # (E, d, f)
    wo: jax.Array,              # (E, f, d)
    slot_tok: jax.Array,        # (E*C, 1) int32
    slot_gate: jax.Array,       # (E*C, 1) f32
    *,
    block_c: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """Dispatch + expert SwiGLU + gate scaling; returns gated (E·C, d) slots."""
    T, d = x.shape
    E, _, f = wg.shape
    S = slot_tok.shape[0]
    C = S // E
    assert S == E * C and slot_gate.shape == (S, 1), (slot_tok.shape, E, C)
    bc = min(block_c, C)
    assert C % bc == 0, (C, bc)
    n_cb = C // bc

    kernel = functools.partial(_fused_moe_kernel, bc=bc, T=T)
    return pl.pallas_call(
        kernel,
        grid=(E, n_cb),
        in_specs=[
            pl.BlockSpec((bc, 1), lambda e, cb, n=n_cb: (e * n + cb, 0)),
            pl.BlockSpec((bc, 1), lambda e, cb, n=n_cb: (e * n + cb, 0)),
            pl.BlockSpec((T, d), lambda e, cb: (0, 0)),
            pl.BlockSpec((1, d, f), lambda e, cb: (e, 0, 0)),
            pl.BlockSpec((1, d, f), lambda e, cb: (e, 0, 0)),
            pl.BlockSpec((1, f, d), lambda e, cb: (e, 0, 0)),
        ],
        out_specs=pl.BlockSpec((bc, d), lambda e, cb, n=n_cb: (e * n + cb, 0)),
        out_shape=jax.ShapeDtypeStruct((S, d), x.dtype),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_GEMM_VMEM_LIMIT
        ),
        interpret=interpret,
    )(slot_tok, slot_gate, x, wg, wu, wo)


def _combine_kernel(
    tok_ref,                    # (bs, 1) int32 slot->token table block
    y_ref,                      # (bs, d) gated expert output block
    o_ref,                      # (bt, d) token-row output block
    acc_ref,                    # (bt, d) f32 scratch, summed over slot blocks
    *,
    bt: int,
    bs: int,
    n_s: int,
):
    it, js = pl.program_id(0), pl.program_id(1)

    @pl.when(js == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    tok = tok_ref[...]                                          # (bs, 1)
    # transposed one-hot: column t of `sel` marks the slots owned by token
    # t0+t; empty slots carry the sentinel token index (>= T) and their y
    # rows are gate-zeroed anyway, so they contribute exact +0.0
    t_iota = it * bt + jax.lax.broadcasted_iota(jnp.int32, (bs, bt), 1)
    sel = (tok == t_iota).astype(y_ref.dtype)                   # (bs, bt)
    acc_ref[...] += jax.lax.dot_general(                        # (bt, d) MXU
        sel, y_ref[...], (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    @pl.when(js == n_s - 1)
    def _emit():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def fused_moe_combine(
    y: jax.Array,               # (E*C, d) gated slot rows
    slot_tok: jax.Array,        # (E*C, 1) int32 (sentinel T for empty slots)
    T: int,
    *,
    capacity: int,
    block_t: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """Combine gated slot rows into (T, d) token rows as a one-hot matmul.

    Bit-exact vs the XLA ``.at[st].add`` scatter: every token sums the same
    <= k gated slot rows, and summing them with interleaved exact zeros is
    the same f32 value as the sequential scatter-add.  Slot blocks divide
    the capacity, so a block lies inside one expert's slots and holds at
    most one copy of any token: the blockwise f32 sum keeps slot order.
    """
    S, d = y.shape
    assert slot_tok.shape == (S, 1) and S % capacity == 0, (
        slot_tok.shape, capacity)
    bs = next(b for b in range(min(capacity, _COMBINE_BLOCK_S), 0, -1)
              if capacity % b == 0 and (b % 8 == 0 or b == capacity))
    bt = min(block_t, max(T, 8))
    pad_t = (-T) % bt
    Tp = T + pad_t
    # padded token rows only ever match the sentinel's gate-zeroed slots (or
    # nothing at all), and are sliced back off below
    n_s = S // bs
    out = pl.pallas_call(
        functools.partial(_combine_kernel, bt=bt, bs=bs, n_s=n_s),
        grid=(Tp // bt, n_s),
        in_specs=[
            pl.BlockSpec((bs, 1), lambda it, js: (js, 0)),
            pl.BlockSpec((bs, d), lambda it, js: (js, 0)),
        ],
        out_specs=pl.BlockSpec((bt, d), lambda it, js: (it, 0)),
        out_shape=jax.ShapeDtypeStruct((Tp, d), y.dtype),
        scratch_shapes=[pltpu.VMEM((bt, d), jnp.float32)],
        interpret=interpret,
    )(slot_tok, y)
    return out[:T]


def _combine_xla(y, st, slot, keep, T, E, C):
    """The scatter-add combine the kernel replaced — kept as the bit-exact
    A/B target (`combine="xla"`) and the off-Pallas fallback."""
    safe_slot = jnp.minimum(slot, E * C - 1)
    out_copies = y[safe_slot] * keep[:, None].astype(y.dtype)
    return jnp.zeros((T, y.shape[1]), y.dtype).at[st].add(out_copies)


def fused_moe_mlp_fwd(
    x: jax.Array,               # (T, d)
    router: jax.Array,          # (d, E)
    wg: jax.Array, wu: jax.Array, wo: jax.Array,
    *,
    k: int,
    capacity: int,
    block_c: int = 128,
    interpret: bool = False,
    combine: str = "kernel",    # "kernel" | "xla" (the A/B + fallback)
) -> Tuple[jax.Array, jax.Array]:
    """Full fused MoE forward: routing → fused kernel → in-kernel combine.

    Returns ``(out (T, d), aux)``; matches
    :func:`repro.kernels.ref.fused_moe_mlp_ref` (parity-tested), and the
    two combine paths match each other bit-exactly (property-tested).
    """
    T, _ = x.shape
    E = router.shape[1]
    C = capacity
    slot_tok, slot_gate, st, slot, keep, aux = moe_routing(x, router, k, C)
    y = fused_moe_gemm(x, wg, wu, wo, slot_tok, slot_gate,
                       block_c=block_c, interpret=interpret)
    if combine == "kernel":
        # gates were applied in-kernel; dropped copies never got a slot and
        # empty slots are gate-zeroed, so the one-hot contraction is the
        # whole combine
        out = fused_moe_combine(y, slot_tok, T, capacity=C,
                                interpret=interpret)
    else:
        out = _combine_xla(y, st, slot, keep, T, E, C)
    return out, aux
