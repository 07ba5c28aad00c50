"""The chip benchmark's harness: one cell, one process.

A cell is an entry of ``BENCHMARK.json``'s ``workloads``.  Everything that
belongs to one configuration, traffic mix or per-layer metric lives in a
file of its own, found by the name ``BENCHMARK.json`` gives it:

    bench/configs/<config>.json     sizes as run (the ``file`` of the config)
    bench/traffic/<traffic>.json    fleet layout, storage, rows, optimizer,
                                    the limits of the correctness check
    bench/metrics/<metric>.py       ``read(ctx) -> float | None``
    bench/reference/<family>.py     the plain reference of a model family

A training run builds the program's ``Session`` from the traffic file,
makes the weights from the seed on the device, drives the compiled step
through its first three steps with ``Session.run`` (set-up; their readings
are what the reference checks), then measures consecutive
``Session.run(params, opt_state=..., steps=k)`` calls for ``--seconds``.
After the window it reads the device's peak memory, frees the program's
state and runs the reference.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import math
import shutil
import statistics
import sys
import tempfile
import time
from functools import partial
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional

ROOT = Path(__file__).resolve().parents[1]

# the first steps are set-up; the reference follows exactly these
CHECK_STEPS = 3
# steps of each Session.run call in the window
STEPS_PER_CALL = 2


# ---------------------------------------------------------------------------
# Finding a cell by name
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: Dict[str, Any]          # bench/configs/<config>.json
    traffic: Dict[str, Any]         # bench/traffic/<traffic>.json
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]
    root: Path


def _load_json(path: Path) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def _applies(metric: Dict[str, Any], cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def find_cell(name: str, root: Path = ROOT) -> Cell:
    spec = _load_json(root / "BENCHMARK.json")
    by_name = {w["name"]: w for w in spec["workloads"]}
    if name not in by_name:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                       f"known: {sorted(by_name)}")
    w = by_name[name]
    cfg_entry = {c["name"]: c for c in spec["configs"]}[w["config"]]
    e2e = [m for m in spec["end_to_end"] if _applies(m, name)]
    reported = {m["name"] for m in e2e}
    per_layer = [
        m for m in spec["per_layer"]
        if m["moves"] in reported and _applies(m, name)
    ]
    return Cell(
        name=name,
        chips=int(w["chips"]),
        config=_load_json(root / cfg_entry["file"]),
        traffic=_load_json(root / "bench" / "traffic" / f"{w['traffic']}.json"),
        end_to_end=e2e,
        per_layer=per_layer,
        root=root,
    )


def _load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_reader(metric: str, root: Path = ROOT) -> Callable:
    return _load_module(root / "bench" / "metrics" / f"{metric}.py",
                        f"bench_metric_{metric}").read


def reference_module(family: str, root: Path = ROOT):
    return _load_module(root / "bench" / "reference" / f"{family}.py",
                        f"bench_reference_{family}")


# ---------------------------------------------------------------------------
# The program under test
# ---------------------------------------------------------------------------


def program_model_config(cfg: Dict[str, Any]):
    """The program's ModelConfig for a configuration file's sizes."""
    import jax.numpy as jnp

    from repro.models.config import ModelConfig

    if cfg.get("hidden_act", "silu") != "silu":
        raise ValueError(f"unsupported hidden_act {cfg['hidden_act']!r}")
    d, h = int(cfg["hidden_size"]), int(cfg["num_attention_heads"])
    return ModelConfig(
        name=cfg["name"],
        family=cfg["family"],
        n_layers=int(cfg["num_hidden_layers"]),
        d_model=d,
        n_heads=h,
        n_kv_heads=int(cfg["num_key_value_heads"]),
        head_dim=int(cfg.get("head_dim") or d // h),
        d_ff=int(cfg["intermediate_size"]),
        vocab=int(cfg["vocab_size"]),
        rope_theta=float(cfg["rope_theta"]),
        mlp="swiglu",
        tie_embeddings=bool(cfg.get("tie_word_embeddings", False)),
        dtype=jnp.dtype(cfg["torch_dtype"]),
        **cfg.get("program", {}),
    )


def build_session(cell: Cell, seed: int, spool_root: Optional[str]):
    from repro.api import FleetSpec, Session, SessionConfig
    from repro.models.api import get_model
    from repro.optim import adamw
    from repro.storage import DataConfig

    tr = cell.traffic
    fleet = dict(tr["fleet"])
    spec = FleetSpec.demo(fleet.pop("n_csds"), **fleet)
    if tr["storage"] == "flash":
        spec = spec.with_storage("flash", root=spool_root)
    elif tr["storage"] == "meshfeed":
        spec = spec.with_storage("meshfeed", data_axis=tr.get("data_axis"))
    mcfg = program_model_config(cell.config)
    opt, sch = tr["optimizer"], tr["schedule"]
    return Session(
        model=get_model(mcfg),
        optimizer=adamw(b1=opt["b1"], b2=opt["b2"], eps=opt["eps"],
                        weight_decay=opt["weight_decay"]),
        fleet=spec,
        data=DataConfig(vocab=mcfg.vocab, seq_len=int(tr["seq_len"]),
                        seed=int(seed)),
        config=SessionConfig(
            total_steps=int(sch["total_steps"]),
            base_lr=float(sch["base_lr"]),
            base_batch=int(sch["base_batch"]),
            warmup_steps=int(sch["warmup_steps"]),
            seed=int(seed) & 0x7FFFFFFF,
        ),
        shards=spec.shards(
            private_per_worker=tr["shards"]["private_per_worker"],
            public=int(tr["shards"]["public"]),
        ),
    )


class Feed:
    """Times ``next_device_batch`` and keeps host copies of the first
    batches (the ones the reference checks)."""

    def __init__(self, dataset, keep: int):
        import jax

        self._next = dataset.next_device_batch
        self._span = jax.profiler.TraceAnnotation
        self.keep = keep
        self.seconds: List[float] = []
        self.kept: List[Dict[str, Any]] = []
        dataset.next_device_batch = self

    def __call__(self):
        import numpy as np

        with self._span("bench.feed"):
            t = time.perf_counter()
            batch = self._next()
            self.seconds.append(time.perf_counter() - t)
        if len(self.kept) < self.keep:
            self.kept.append({k: np.asarray(batch[k])
                              for k in ("tokens", "loss_mask")})
        return batch


class CompileCounter:
    """Backend compiles reported by JAX while active."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.count = 0
        self.active = False

    def __call__(self, event: str, duration: float, **_: Any) -> None:
        if self.active and event == self.EVENT:
            self.count += 1


def _leaf_norms(tree, scale: float = 1.0) -> Dict[str, float]:
    import jax
    import jax.numpy as jnp

    norm = jax.jit(lambda x: jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)))))
    out = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        key = "/".join(str(getattr(k, "key", k)) for k in path)
        out[key] = float(norm(leaf)) * scale
    return out


def _change_norms(params, start) -> Dict[str, float]:
    import jax
    import jax.numpy as jnp

    dn = jax.jit(lambda a, b: jnp.sqrt(jnp.sum(jnp.square(
        a.astype(jnp.float32) - b.astype(jnp.float32)))))
    a = jax.tree_util.tree_flatten_with_path(params)[0]
    b = jax.tree_util.tree_leaves(start)
    return {"/".join(str(getattr(k, "key", k)) for k in path): float(dn(x, y))
            for (path, x), y in zip(a, b)}


@dataclasses.dataclass
class Readings:
    """What the program did in its first steps, read from its own state."""

    groups: List[List[Any]]
    losses: List[float]
    grad_norms: Dict[str, float]
    change_norms: Dict[str, float]
    batches: List[Dict[str, Any]]


def setup_program(cell: Cell, seed: int, devices, spool_root: Optional[str],
                  plant: Optional[Callable] = None):
    """Build the session, make the weights, run the checked first steps.

    Returns (session, params, opt_state, feed, readings): the same compiled
    step and state go on into the window.  ``plant`` (tests only) receives
    the session after its step is compiled, to break the timed path."""
    import jax

    ref = reference_module(cell.config["family"], cell.root)
    session = build_session(cell, seed, spool_root)
    tp = session.tune()
    groups = [[w, int(b)] for w, b in zip(tp.group_workers,
                                          tp.schedule.group_batches)]
    plan = session.shard()
    abstract = session.model.init_params(abstract=True)[0]
    key = ref.seed_key(seed)
    init = jax.jit(partial(ref.init_params, cell.config),
                   out_shardings=plan.params)
    if jax.tree_util.tree_structure(jax.eval_shape(init, key)) != \
            jax.tree_util.tree_structure(abstract):
        raise RuntimeError("the reference's parameter tree does not match "
                           "the program's")
    params = init(key)
    opt_state = jax.jit(session.optimizer.init,
                        out_shardings=plan.opt)(params)
    session.compile()
    if plant is not None:
        plant(session)
    feed = Feed(session.dataset, keep=CHECK_STEPS)

    b1 = float(cell.traffic["optimizer"]["b1"])
    rep = session.run(params, opt_state=opt_state, steps=1)
    losses = [h["loss"] for h in rep.history]
    # the first gradient as the optimizer got it: mu_1 = (1 - b1) g_1
    grad_norms = _leaf_norms(rep.opt_state.mu, 1.0 / (1.0 - b1))
    rep = session.run(rep.params, opt_state=rep.opt_state,
                      steps=CHECK_STEPS - 1)
    losses += [h["loss"] for h in rep.history]
    start = init(key)
    change = _change_norms(rep.params, start)
    del start
    readings = Readings(groups, losses, grad_norms, change, feed.kept)
    return session, rep.params, rep.opt_state, feed, readings


# ---------------------------------------------------------------------------
# The correctness check
# ---------------------------------------------------------------------------


def worst_leaf_gap(prog: Dict[str, float], ref: Dict[str, float],
                   ref_grad: Dict[str, float]):
    """Largest |program norm - reference norm| over leaves, each against
    the larger of its reference norm and the median leaf's.  Leaves whose
    reference gradient is under a thousandth of the median leaf's are left
    out: they move under Adam by round-off alone."""
    med_g = statistics.median(ref_grad.values())
    kept = [k for k in ref if ref_grad[k] >= 1e-3 * med_g]
    med = statistics.median(ref[k] for k in kept)
    worst, where = 0.0, None
    for k in kept:
        if k not in prog or not math.isfinite(prog[k]):
            return math.inf, k
        denom = max(ref[k], med)
        gap = abs(prog[k] - ref[k]) / denom if denom > 0 else abs(prog[k])
        if gap > worst:
            worst, where = gap, k
    return worst, where


def feed_mismatches(cell: Cell, ref, seed: int,
                    batches: List[Dict[str, Any]]) -> int:
    """Rows of the first steps' batches that differ from what the traffic's
    layout says the step should get (tokens of valid rows; a zero loss mask
    on padding rows and a full one on valid rows)."""
    import numpy as np

    ml, valid = ref.padded_layout(cell.traffic)
    bad = 0
    for t, b in enumerate(batches):
        want = ref.step_rows(cell.config, cell.traffic, seed, t)
        toks, mask = b["tokens"], b["loss_mask"]
        if toks.shape[0] != valid.size:
            return valid.size * len(batches)
        got = toks[valid]
        bad += int(np.sum(np.any(got != want[:, :-1], axis=1)))
        bad += int(np.sum(np.any(mask[valid] != 1.0, axis=1)))
        bad += int(np.sum(np.any(mask[~valid] != 0.0, axis=1)))
    return bad


def compare(cell: Cell, readings: Readings, ref_out: Dict[str, Any],
            feed_bad: int) -> Dict[str, Dict[str, float]]:
    """Each number compared, beside its limit from the traffic file."""
    lim = cell.traffic["limits"]
    # a non-finite gap (a NaN loss) is shown as the largest float: JSON has
    # no infinity
    big = lambda x: x if math.isfinite(x) else sys.float_info.max
    out = {
        "layout_differs": {
            "value": int(readings.groups != cell.traffic["groups"]),
            "limit": 0},
        "feed_rows_wrong": {"value": feed_bad, "limit": 0},
    }
    for name, value in gaps(readings.losses, readings.grad_norms,
                            readings.change_norms, ref_out).items():
        out[name] = {"value": big(value), "limit": lim[name]}
    return out


def gaps(losses: List[float], grad_norms: Dict[str, float],
         change_norms: Dict[str, float], ref_out: Dict[str, Any]
         ) -> Dict[str, float]:
    """Each step's relative loss gap (``loss_gap_<step>``: the first reads
    the forward pass alone, later ones the updates too), the worst leaf's
    gap of first-gradient norms and of change norms."""
    out = {
        f"loss_gap_{t + 1}": (abs(p - r) / abs(r) if math.isfinite(p)
                              else math.inf)
        for t, (p, r) in enumerate(zip(losses, ref_out["losses"]))
    }
    out["grad_gap"] = worst_leaf_gap(grad_norms, ref_out["grad_norms"],
                                     ref_out["grad_norms"])[0]
    out["change_gap"] = worst_leaf_gap(change_norms, ref_out["change_norms"],
                                       ref_out["grad_norms"])[0]
    return out


# ---------------------------------------------------------------------------
# One run of a cell
# ---------------------------------------------------------------------------


def free_device_state() -> None:
    import jax

    gc.collect()
    for a in jax.live_arrays():
        a.delete()
    jax.clear_caches()
    gc.collect()


def _peak_bytes(devices) -> int:
    """Peak bytes on the fullest device.  The TPU runtime keeps a compiled
    program's temporaries in a reserved region that ``peak_bytes_in_use``
    does not count, so the two peaks are added (an upper bound: on the
    training step they coincide)."""
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0))
                     + int(stats.get("peak_bytes_reserved", 0)))
    return max(peaks) if peaks else 0


def run_cell(
    cell: Cell,
    seed: int,
    seconds: float,
    trace: bool,
    *,
    t_start: float,
    devices=None,
    plant: Optional[Callable] = None,
    log: Callable[[str], None] = lambda s: print(s, file=sys.stderr),
) -> Dict[str, Any]:
    """Set up, measure for ``seconds``, check; return the result line."""
    import jax

    from bench import flops as F
    from bench import trace_reduce as TR

    devices = list(devices or jax.devices()[: cell.chips])
    kind = devices[0].device_kind
    tr = cell.traffic
    seq = int(tr["seq_len"])
    counter = CompileCounter()
    jax.monitoring.register_event_duration_secs_listener(counter)
    spool = tempfile.mkdtemp(prefix="bench-spool-") \
        if tr["storage"] == "flash" else None
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    try:
        session, params, opt_state, feed, readings = setup_program(
            cell, seed, devices, spool, plant)
        sched = session.tune().schedule
        valid_rows, global_rows = sched.valid_rows, sched.global_rows
        feed.seconds.clear()

        # ---- the measured window ----
        # set-up leaves ~10^5 Python objects; a full collection that scans
        # them stalls the host (and so the device, which waits on the
        # per-step sync) for ~70 ms at a random point of some windows.
        # Frozen, they are no longer scanned; new garbage still is.
        gc.collect()
        gc.freeze()
        if trace:
            # no Python-function tracer: it would slow the host loop being
            # measured; host spans come from TraceMe annotations alone
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
        counter.active = True
        t0 = time.perf_counter()
        setup_s = t0 - t_start
        steps, losses, call_s = 0, [], []
        while True:
            t_call = time.perf_counter()
            with jax.profiler.TraceAnnotation("bench.run"):
                rep = session.run(params, opt_state=opt_state,
                                  steps=STEPS_PER_CALL)
            params, opt_state = rep.params, rep.opt_state
            steps += rep.steps_run
            losses += [h["loss"] for h in rep.history]
            call_s.append(time.perf_counter() - t_call)
            if time.perf_counter() - t0 >= seconds:
                break
        window_s = time.perf_counter() - t0
        gc.unfreeze()
        counter.active = False
        if trace:
            jax.profiler.stop_trace()
        window_compiles = counter.count
        memory_peak = _peak_bytes(devices)
        feed_s = list(feed.seconds)
        del rep, params, opt_state, session, feed
        free_device_state()

        # ---- the reference, once the program's state is gone ----
        ref = reference_module(cell.config["family"], cell.root)
        t_ref = time.perf_counter()
        ref_out = ref.train_readings(cell.config, tr, seed,
                                     steps=CHECK_STEPS, devices=devices)
        ref_s = time.perf_counter() - t_ref
        feed_bad = feed_mismatches(cell, ref, seed, readings.batches)
        checks = compare(cell, readings, ref_out, feed_bad)
        failed = sum(1 for x in losses if not math.isfinite(x))
        # a limit of null: the number is read and shown but not compared
        # (no control or fault separates it from sound runs)
        correct = failed == 0 and all(
            c["value"] <= c["limit"] for c in checks.values()
            if c["limit"] is not None)

        valid_tokens = steps * valid_rows * seq
        result: Dict[str, Any] = {
            "correct": bool(correct),
            "attempted": steps,
            "failed": failed,
        }
        device = {
            "platform": devices[0].platform,
            "kind": kind,
            "count": len(jax.devices()),
            "memory_peak_bytes": memory_peak,
        }
        breakdown = None
        if trace:
            summary = TR.reduce_dir(trace_dir, n_devices=len(devices))
            device["busy_s"] = summary.busy_s
            device["window_s"] = summary.window_s
            breakdown = {"device_ops": summary.top_ops,
                         "idle_gaps": summary.idle_gaps}
            ctx = {
                "chips": len(devices),
                "window_s": window_s,
                "steps": steps,
                "valid_tokens": valid_tokens,
                "valid_rows": valid_rows,
                "global_rows": global_rows,
                "feed_s": feed_s,
                "trace": summary,
                "flops_per_token": F.train_flops_per_token(cell.config, seq),
                "peak": F.peak(kind, cell.root),
            }
            metrics = {}
            for m in cell.per_layer:
                value = metric_reader(m["name"], cell.root)(ctx)
                if value is not None:
                    metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        else:
            metrics = {}
            values = {
                "setup_s": setup_s,
                "train_tokens_per_s": valid_tokens / window_s,
            }
            for m in cell.end_to_end:
                metrics[m["name"]] = {"value": values[m["name"]],
                                      "unit": m["unit"]}
        result["metrics"] = metrics
        result["device"] = device
        if breakdown is not None:
            result["breakdown"] = breakdown
        result["checks"] = checks

        log(f"{cell.name} seed={seed}: setup {setup_s:.3f} s, window "
            f"{window_s:.3f} s, {steps} steps in {len(call_s)} calls "
            f"(median {statistics.median(call_s):.3f} s, longest "
            f"{max(call_s):.3f} s, call {call_s.index(max(call_s)) + 1}), "
            f"{valid_tokens} valid tokens, compiles in window "
            f"{window_compiles}, reference {ref_s:.1f} s")
        log(f"losses program {readings.losses} reference {ref_out['losses']}")
        return result
    finally:
        jax.monitoring.unregister_event_duration_listener(counter)
        for d in (spool, trace_dir):
            if d:
                shutil.rmtree(d, ignore_errors=True)
