"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

Proves the distribution config is coherent without hardware:
  * 512 placeholder CPU devices host the production meshes
    (16, 16) = one pod and (2, 16, 16) = two pods.
  * Params/optimizer/caches are ShapeDtypeStructs — nothing is allocated.
  * For each cell we ``jit(step).lower(...).compile()`` and record
    memory_analysis (fits?), cost_analysis (FLOPs/bytes), and the collective
    bytes parsed from the HLO — the roofline inputs.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun                    # all cells, single-pod
  PYTHONPATH=src python -m repro.launch.dryrun --multi-pod        # 2-pod mesh
  PYTHONPATH=src python -m repro.launch.dryrun --arch dbrx-132b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --out results.json
"""
from __future__ import annotations

import os

# MUST run before any jax import: jax locks the device count on first init.
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

import argparse
import json
import sys
import time
import traceback
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import ARCHS, get_config
from repro.configs.shapes import SHAPES, ShapeSpec, applicable, input_specs
from repro.distributed.sharding import (
    arg_shardings_for_tree, make_rules, set_rules, specs_for_tree,
)
from repro.launch.mesh import make_production_mesh
from repro.models.api import get_model
from repro.optim import adamw
from repro.roofline.collectives import collective_bytes_from_hlo
from repro.train.steps import (
    abstract_opt_state, make_serve_step, make_train_step,
)

SDS = jax.ShapeDtypeStruct


def _batch_axes(batch: Dict[str, Any]) -> Dict[str, Any]:
    ax = {}
    for k, v in batch.items():
        if k in ("tokens", "labels", "loss_mask"):
            ax[k] = ("batch", "seq_data")      # batch over (pod, data)
        elif k in ("frames", "patch_embeds"):
            ax[k] = ("batch", None, "act_embed")
        elif k == "token":
            ax[k] = ("batch", None)
        elif k == "pos":
            ax[k] = ("batch",)
        else:
            raise KeyError(k)
    return ax


def _cycle_len(cfg) -> int:
    """Layers per repeating pattern cycle (cost-calibration unit)."""
    if cfg.family == "rglru":
        return len(cfg.block_pattern or ("R", "R", "A"))
    return 1


def _with_layers(cfg, n: int):
    """Full-dims config with ``n`` layers, UNROLLED (exact cost_analysis)."""
    kw = dict(n_layers=n, scan_layers=False)
    if cfg.family == "encdec":
        kw.update(n_enc_layers=n, n_dec_layers=n)
    return cfg.with_(**kw)


def dryrun_cell(
    arch: str,
    shape_name: str,
    *,
    multi_pod: bool = False,
    rules_overrides: Optional[Dict[str, Any]] = None,
    verbose: bool = True,
    calibrate: bool = True,
    zero1: bool = False,
) -> Dict[str, Any]:
    """Lower + compile one cell; returns the roofline record.

    Two-phase costing: the FULL config (scan-over-layers) proves
    shardability + memory; because XLA's cost_analysis counts a scan body
    once, FLOPs/bytes/collectives come from a two-point calibration —
    unrolled 1-cycle and 2-cycle variants at full dims, extrapolated
    linearly to the real depth (exact: unrolled HLO cost is affine in depth).
    """
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    ok, why = applicable(cfg, shape)
    if not ok:
        return {"arch": arch, "shape": shape_name, "status": "skipped", "why": why}

    mesh = make_production_mesh(multi_pod=multi_pod)

    seq_shard = bool(shape.long_context)
    # zero1: params stay UN-sharded over data (no per-layer ZeRO-3 gathers);
    # only the optimizer state shards over data — GSPMD then emits a single
    # grads-reduce-scatter + params-all-gather around the update, once per
    # step instead of 2 gathers + 1 scatter per LAYER.
    rules = make_rules(fsdp=cfg.fsdp and not zero1, seq_shard=seq_shard,
                       extra=(rules_overrides or None))
    # token batch rows shard over every dp-ish axis; seq_data is the token/seq
    # dim of the *batch* (sharded only for SP long-context)
    rules.setdefault("seq_data", "data" if seq_shard else None)
    set_rules(rules)
    opt_rules = (
        make_rules(fsdp=True, seq_shard=seq_shard, extra=(rules_overrides or None))
        if zero1 else None
    )
    if opt_rules is not None:
        opt_rules.setdefault("seq_data", "data" if seq_shard else None)
        # opt state must not inherit a batch-over-model override
        opt_rules["batch"] = ("pod", "data")

    t0 = time.time()
    compiled = _lower_and_compile(cfg, shape, mesh, rules, opt_rules=opt_rules)
    elapsed = time.time() - t0
    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    coll = collective_bytes_from_hlo(compiled.as_text())
    n_dev = mesh.devices.size

    flops = float(cost.get("flops", 0.0))
    hbm = float(cost.get("bytes accessed", 0.0))
    calibration = None
    if calibrate:
        # XLA cost_analysis counts scan bodies ONCE -> calibrate with
        # unrolled 1-cycle / 2-cycle variants at full dims and extrapolate.
        c = _cycle_len(cfg)
        layers = cfg.n_enc_layers if cfg.family == "encdec" else cfg.n_layers
        cyc = layers // c
        c1 = _lower_and_compile(_with_layers(cfg, c), shape, mesh, rules,
                                opt_rules=opt_rules)
        c2 = _lower_and_compile(_with_layers(cfg, 2 * c), shape, mesh, rules,
                                opt_rules=opt_rules)
        f1 = float(c1.cost_analysis().get("flops", 0.0))
        f2 = float(c2.cost_analysis().get("flops", 0.0))
        b1 = float(c1.cost_analysis().get("bytes accessed", 0.0))
        b2 = float(c2.cost_analysis().get("bytes accessed", 0.0))
        k1 = collective_bytes_from_hlo(c1.as_text())
        k2 = collective_bytes_from_hlo(c2.as_text())
        flops = f1 + (cyc - 1) * (f2 - f1)
        hbm = b1 + (cyc - 1) * (b2 - b1)
        kinds = set(k1) | set(k2)
        coll = {
            k: int(k1.get(k, 0) + (cyc - 1) * (k2.get(k, 0) - k1.get(k, 0)))
            for k in kinds
        }
        coll = {k: max(0, v) for k, v in coll.items()}
        calibration = {
            "cycle_layers": c, "cycles": cyc,
            "flops_1": f1, "flops_2": f2, "bytes_1": b1, "bytes_2": b2,
        }

    rec = {
        "arch": arch,
        "shape": shape_name,
        "mesh": "multi_pod" if multi_pod else "single_pod",
        "status": "ok",
        "n_devices": int(n_dev),
        "compile_s": round(elapsed, 1),
        "flops": flops,
        "hbm_bytes": hbm,
        "collective_bytes": coll,
        "memory": {
            k: int(getattr(mem, k))
            for k in ("argument_size_in_bytes", "output_size_in_bytes",
                      "temp_size_in_bytes", "generated_code_size_in_bytes")
            if hasattr(mem, k)
        },
        "params": int(cfg.param_count()),
        "active_params": int(cfg.param_count(active_only=True)),
        "calibration": calibration,
    }
    if verbose:
        per_dev = (rec["memory"].get("argument_size_in_bytes", 0)
                   + rec["memory"].get("temp_size_in_bytes", 0)) / n_dev
        print(
            f"[{rec['mesh']}] {arch} x {shape_name}: OK "
            f"({elapsed:.0f}s compile, {rec['flops']:.3e} flops, "
            f"coll {sum(coll.values()):.3e} B, ~{per_dev/2**30:.2f} GiB/dev)"
        )
    return rec


def _lower_and_compile(cfg, shape, mesh, rules, opt_rules=None):
    """Lower + compile the step function for (cfg, shape) under (mesh, rules).

    ``opt_rules``: separate rule table for the optimizer state (ZeRO-1)."""
    model = get_model(cfg)
    params, p_axes = model.init_params(abstract=True)
    p_shardings = arg_shardings_for_tree(p_axes, params, rules, mesh)
    batch = input_specs(cfg, shape)

    with jax.set_mesh(mesh):
        if shape.kind == "train":
            opt = adamw()
            step = make_train_step(model, opt, lambda s: jnp.float32(1e-3))
            from repro.optim.optimizers import OptState

            opt_state = abstract_opt_state(opt, params)
            m_shardings = (
                arg_shardings_for_tree(p_axes, params, opt_rules, mesh)
                if opt_rules is not None else p_shardings
            )
            o_shardings = OptState(
                step=NamedSharding(mesh, P()),
                mu=m_shardings,
                nu=m_shardings,
            )
            b_axes = _batch_axes(batch)
            b_shardings = arg_shardings_for_tree(b_axes, batch, rules, mesh)
            lowered = jax.jit(
                step,
                in_shardings=(p_shardings, o_shardings, b_shardings),
                donate_argnums=(0, 1),
            ).lower(params, opt_state, batch)
        elif shape.kind == "prefill":
            def prefill_step(params, batch):
                kwargs = {k: v for k, v in batch.items() if k != "tokens"}
                return model.prefill(params, batch["tokens"], shape.seq_len, **kwargs)

            b_axes = _batch_axes(batch)
            b_shardings = arg_shardings_for_tree(b_axes, batch, rules, mesh)
            lowered = jax.jit(
                prefill_step, in_shardings=(p_shardings, b_shardings)
            ).lower(params, batch)
        else:  # decode
            serve = make_serve_step(model)
            cache = batch["cache"]
            c_axes = model.cache_logical_axes()
            c_shardings = arg_shardings_for_tree(c_axes, cache, rules, mesh)
            tok_sh = arg_shardings_for_tree(
                {"token": ("batch", None), "pos": ("batch",)},
                {"token": batch["token"], "pos": batch["pos"]}, rules, mesh,
            )
            lowered = jax.jit(
                serve,
                in_shardings=(
                    p_shardings, tok_sh["token"], c_shardings, tok_sh["pos"]
                ),
                donate_argnums=(2,),
            ).lower(params, batch["token"], cache, batch["pos"])

        return lowered.compile()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None, help="one arch id (default: all)")
    ap.add_argument("--shape", default=None, help="one shape name (default: all)")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--out", default=None, help="write JSON results here")
    args = ap.parse_args(argv)

    archs = [args.arch] if args.arch else ARCHS
    shapes = [args.shape] if args.shape else list(SHAPES)
    meshes = [False, True] if args.both_meshes else [args.multi_pod]

    results, failures = [], 0
    for multi in meshes:
        for arch in archs:
            for shape in shapes:
                try:
                    rec = dryrun_cell(arch, shape, multi_pod=multi)
                except Exception as e:
                    failures += 1
                    rec = {
                        "arch": arch, "shape": shape,
                        "mesh": "multi_pod" if multi else "single_pod",
                        "status": "error", "error": f"{type(e).__name__}: {e}",
                    }
                    print(f"[{'multi' if multi else 'single'}] {arch} x {shape}: "
                          f"FAIL {type(e).__name__}: {str(e)[:200]}")
                    traceback.print_exc(limit=3)
                results.append(rec)
    if args.out:
        with open(args.out, "w") as f:
            json.dump(results, f, indent=1)
        print(f"wrote {len(results)} records to {args.out}")
    n_ok = sum(1 for r in results if r["status"] == "ok")
    n_skip = sum(1 for r in results if r["status"] == "skipped")
    print(f"dry-run: {n_ok} ok, {n_skip} skipped, {failures} failed")
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
