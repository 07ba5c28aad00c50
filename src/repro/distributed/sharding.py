"""Logical-axis -> mesh-axis sharding rules (t5x-style), per architecture.

Every parameter/activation carries a tuple of *logical* axis names (assigned by the
model code via :mod:`repro.models.param`).  A rule table maps logical names to mesh
axes; unlisted names are replicated.  This keeps DP/TP/EP/FSDP/SP decisions in ONE
place per arch and makes §Perf sharding hillclimbs a one-line change.

Mesh axes (production): ``("pod", "data", "model")`` multi-pod or ``("data",
"model")`` single pod.  Smoke tests use a 1-device mesh with the same axis names so
the same code paths run everywhere.
"""
from __future__ import annotations

import contextlib
import dataclasses
import warnings
from typing import Any, Dict, Optional, Sequence, Tuple, Union

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.compat import get_abstract_mesh

PyTree = Any
MeshAxes = Union[None, str, Tuple[str, ...]]


@dataclasses.dataclass(frozen=True)
class ShardingPlan:
    """The resolved placement contract between data, params, and the step.

    ``Session.shard()`` resolves the logical-axis rule table below against
    the live mesh ONCE (see :func:`repro.train.steps.build_sharding_plan`),
    yielding ``NamedSharding`` trees for every jit argument.  Everything
    downstream consumes this artifact instead of re-deriving layouts:

      * ``Session.compile()`` passes ``params``/``opt``/``batch`` as
        explicit ``in_shardings`` (and ``params``/``opt``/``replicated`` as
        ``out_shardings``) — the step is sharding-explicit, not
        GSPMD-implicit.
      * model init is jitted with ``out_shardings=plan.params`` so parameters
        materialize directly as mesh shards (never host-replicated).
      * the meshfeed storage backend lands batch rows with ``plan.batch``
        instead of rebuilding its own layout.
      * checkpoint restore places leaves straight onto ``params``/``opt``
        for ANY mesh shape (elastic save-at-dp=8 / restore-at-dp=4).

    The plan is keyed by ``global_rows``: an elastic event that changes the
    row count resizes the mesh, which invalidates (and re-derives) the plan.
    """

    mesh: Any                 # the live jax.sharding.Mesh
    rules: Dict[str, Any]     # logical axis -> mesh axes, as resolved
    params: PyTree            # NamedSharding tree matching the param pytree
    opt: Any                  # OptState of NamedShardings (step replicated)
    batch: Dict[str, Any]     # NamedSharding per batch key (tokens/labels/..)
    replicated: Any           # NamedSharding(mesh, P()) — metrics/out prefix
    global_rows: int
    data_axis: int            # |mesh["data"]| — how many ways rows shard

    @property
    def n_devices(self) -> int:
        return int(self.mesh.devices.size)

    def signature(self) -> Tuple[int, int, int]:
        return (self.global_rows, self.data_axis, self.n_devices)

    def describe(self) -> str:
        return (
            f"ShardingPlan(mesh={dict(self.mesh.shape)}, "
            f"rows={self.global_rows}, data_axis={self.data_axis})"
        )

# ---------------------------------------------------------------------------
# Rule tables
# ---------------------------------------------------------------------------

# Baseline rules: tensor-parallel over "model", batch over ("pod","data").
# fsdp=True additionally shards the big weight matrices' embed/ff axes over "data"
# (ZeRO-3 style: XLA all-gathers them per layer under scan).
def make_rules(
    *,
    fsdp: bool = False,
    seq_shard: bool = False,
    extra: Optional[Dict[str, MeshAxes]] = None,
) -> Dict[str, MeshAxes]:
    rules: Dict[str, MeshAxes] = {
        # -- weights --
        "layers": None,            # stacked-layer leading dim: never sharded
        "embed": "data" if fsdp else None,   # d_model rows of big matrices
        "vocab": "model",          # embedding/logit vocab dim
        "heads": "model",          # query heads
        "kv_heads": "model",       # kv heads (GSPMD pads if < |model|)
        "head_dim": None,
        "mlp": "model",            # ffn hidden
        "experts": "model",        # MoE expert dim (EP)
        "expert_mlp": None,        # per-expert ffn hidden
        "lru": "model",            # RG-LRU / RWKV channel blocks
        "conv": None,
        "pos": None,
        "norm": None,
        # -- activations --
        "batch": ("pod", "data"),
        "seq": "data" if seq_shard else None,  # SP for long-context decode
        "act_embed": None,
        "act_heads": "model",
        "act_kv_heads": "model",
        "act_mlp": "model",
        "act_vocab": "model",
        "act_experts": "model",
        "kv_seq": "data" if seq_shard else None,  # KV-cache seq dim (SP)
    }
    if extra:
        rules.update(extra)
    return rules


def spec_for(axes: Tuple[Optional[str], ...], rules: Dict[str, MeshAxes]) -> P:
    """Map a tuple of logical axis names to a PartitionSpec."""
    parts = []
    used: set = set()

    def _usable(m: MeshAxes):
        if m is None:
            return None
        if isinstance(m, str):
            return None if m in used else m
        got = tuple(a for a in m if a not in used)
        return got if got else None

    for name in axes:
        mesh_axes = rules.get(name) if name is not None else None
        mesh_axes = _usable(mesh_axes)
        if mesh_axes is None:
            parts.append(None)
        else:
            if isinstance(mesh_axes, str):
                used.add(mesh_axes)
            else:
                used.update(mesh_axes)
            parts.append(mesh_axes)
    while parts and parts[-1] is None:
        parts.pop()
    return P(*parts)


def specs_for_tree(axes_tree: PyTree, rules: Dict[str, MeshAxes]) -> PyTree:
    """Map a pytree of logical-axis tuples to a pytree of PartitionSpecs."""
    return jax.tree_util.tree_map(
        lambda axes: spec_for(axes, rules),
        axes_tree,
        is_leaf=lambda x: isinstance(x, tuple)
        and all(isinstance(e, str) or e is None for e in x),
    )


def shardings_for_tree(
    axes_tree: PyTree, rules: Dict[str, MeshAxes], mesh: Mesh
) -> PyTree:
    specs = specs_for_tree(axes_tree, rules)
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s),
        specs,
        is_leaf=lambda x: isinstance(x, P),
    )


def _divisible_spec(spec: P, shape: Tuple[int, ...], mesh: Mesh) -> P:
    """Drop mesh axes whose product does not divide the dim size.

    jit ARGUMENT shardings must divide exactly (GSPMD pads only intermediate
    constraints), so e.g. a 56-head weight on a 16-way model axis falls back
    to replicated on that dim — its memory footprint is then carried by the
    other (FSDP/vocab/mlp) dims, and the *compute* still shards through the
    uneven activation constraints in the model code.
    """
    parts = []
    for i, part in enumerate(spec):
        if part is None or i >= len(shape):
            parts.append(None)
            continue
        axes = (part,) if isinstance(part, str) else tuple(part)
        keep = []
        prod = 1
        for a in axes:
            if a not in mesh.shape:
                continue  # axis absent in this (smaller) mesh
            n = mesh.shape[a]
            if shape[i] % (prod * n) == 0:
                keep.append(a)
                prod *= n
        parts.append(tuple(keep) if len(keep) > 1 else (keep[0] if keep else None))
    while parts and parts[-1] is None:
        parts.pop()
    return P(*parts)


def arg_shardings_for_tree(
    axes_tree: PyTree, shapes_tree: PyTree, rules: Dict[str, MeshAxes], mesh: Mesh
) -> PyTree:
    """NamedShardings for jit arguments: size-aware (divisibility-safe).

    ``shapes_tree`` carries the leaf shapes (arrays or ShapeDtypeStructs in
    the same structure as ``axes_tree``).
    """
    specs = specs_for_tree(axes_tree, rules)
    is_spec = lambda x: isinstance(x, P)
    shapes = jax.tree_util.tree_leaves(shapes_tree)
    flat_specs = jax.tree_util.tree_leaves(specs, is_leaf=is_spec)
    assert len(shapes) == len(flat_specs), (len(shapes), len(flat_specs))
    fixed = [
        NamedSharding(mesh, _divisible_spec(s, tuple(l.shape), mesh))
        for s, l in zip(flat_specs, shapes)
    ]
    treedef = jax.tree_util.tree_structure(specs, is_leaf=is_spec)
    return jax.tree_util.tree_unflatten(treedef, fixed)


# ---------------------------------------------------------------------------
# Activation constraint helper
# ---------------------------------------------------------------------------

_CURRENT_RULES: Dict[str, MeshAxes] = make_rules()
_CONSTRAIN = True


def set_rules(rules: Dict[str, MeshAxes], constrain: bool = True) -> None:
    global _CURRENT_RULES, _CONSTRAIN
    _CURRENT_RULES = rules
    _CONSTRAIN = constrain


def get_rules() -> Dict[str, MeshAxes]:
    return _CURRENT_RULES


@contextlib.contextmanager
def use_rules(rules: Dict[str, MeshAxes], constrain: bool = True):
    """Temporarily install a rule table (and restore the previous one).

    ``Session.compile()`` traces the step under the ShardingPlan's rules so
    the in-model activation constraints (:func:`with_logical_constraint`)
    resolve against the SAME table that produced the argument shardings —
    including any ``FleetSpec.with_sharding`` overrides.
    """
    global _CURRENT_RULES, _CONSTRAIN
    prev_rules, prev_constrain = _CURRENT_RULES, _CONSTRAIN
    _CURRENT_RULES, _CONSTRAIN = rules, constrain
    try:
        yield
    finally:
        _CURRENT_RULES, _CONSTRAIN = prev_rules, prev_constrain


def mesh_spec(mesh, *axes: Optional[str]) -> P:
    """The PartitionSpec that logical ``axes`` take on ``mesh`` under the
    current rule table, naming only the mesh axes ``mesh`` has (a smaller
    mesh drops the others)."""
    axis_names = set(mesh.axis_names)
    clean = []
    for part in spec_for(tuple(axes), _CURRENT_RULES):
        if part is None:
            clean.append(None)
        elif isinstance(part, str):
            clean.append(part if part in axis_names else None)
        else:
            kept = tuple(a for a in part if a in axis_names)
            clean.append(kept if kept else None)
    return P(*clean)


def with_logical_constraint(x: jax.Array, *axes: Optional[str]) -> jax.Array:
    """``with_sharding_constraint`` by logical axis names; no-op outside a mesh."""
    if not _CONSTRAIN:
        return x
    mesh = get_abstract_mesh()
    if mesh is None:
        return x
    spec = mesh_spec(mesh, *axes)
    try:
        return jax.lax.with_sharding_constraint(x, spec)
    except (ValueError, TypeError) as e:
        # Only the expected constraint failures (rank/axis mismatches) are
        # tolerable — and even those get ONE warning per (spec, mesh) so a
        # rule-table typo can't silently replicate a tensor forever.
        _warn_constraint_skipped(tuple(axes), spec, mesh, e)
        return x


_WARNED_CONSTRAINTS: set = set()


def reset_constraint_warnings() -> None:
    """Clear the warn-once cache of :func:`with_logical_constraint`.

    The cache is process-global by design (a production run warns once per
    (spec, mesh), ever), which makes the WARNING itself order-dependent in
    a test suite: whichever test first triggers a given key eats the
    warning for everyone after it.  Tests that assert the warning call this
    first so the assertion holds under any test ordering.
    """
    _WARNED_CONSTRAINTS.clear()


def _warn_constraint_skipped(axes, clean, mesh, err) -> None:
    key = (
        tuple(axes),
        tuple(tuple(p) if isinstance(p, tuple) else p for p in clean),
        tuple(mesh.axis_names),
        tuple(int(mesh.shape[a]) for a in mesh.axis_names),
    )
    if key in _WARNED_CONSTRAINTS:
        return
    _WARNED_CONSTRAINTS.add(key)
    warnings.warn(
        f"sharding constraint for logical axes {tuple(axes)} "
        f"(spec {P(*clean)}) skipped on mesh "
        f"{dict(mesh.shape)}: {type(err).__name__}: {err}",
        RuntimeWarning,
        stacklevel=3,
    )
