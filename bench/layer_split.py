"""Where a traced cell's device idle time and device time go, by layer.

    python3 bench/layer_split.py --workload <name> --seed <n> --seconds <s>

Runs the cell as ``bench/run.py ... --trace 1`` does: the same set-up,
traced window and correctness check, and the same result line on standard
output.  From the same trace, the compiled step's optimized HLO text and
the window's ``TrainReport``s it then prints one more JSON line,
``{"layer_split": {...}}``, every number per step of the window:

* ``idle_ms``: the first device's idle milliseconds, split into
  ``idle_feed_ms`` (under the program's ``train.feed`` span),
  ``idle_readback_ms`` (``train.readback``), ``idle_loop_ms``
  (``train.prepare``, ``train.dispatch`` and ``train.control``) and
  ``idle_other_ms`` (under none of them);
* ``busy_ms``: device op self time, mean over the chips, split by the
  named scope of each op's ``op_name`` into ``attention_ms``, ``mlp_ms``,
  ``vocab_ms``, ``optimizer_ms`` and ``other_ms``;
* ``readbacks_per_step``: the window's ``TrainReport.readbacks`` over its
  steps.

To standard error it adds the longest window call's seconds in each span
of the step loop, summed from its ``history``.  The benchmark's own metrics
do not read these numbers: its metric readers get the trace's summary
alone (PERF.md, Open questions).
"""
from __future__ import annotations

import contextlib
import json
import sys
import time
from pathlib import Path

sys.path[:0] = [str(Path(__file__).resolve().parent)]

import run  # noqa: E402  (sets the compile cache and the import path)

from bench import harness as H  # noqa: E402
from bench import trace_reduce as TR  # noqa: E402
from bench import trace_scopes as TS  # noqa: E402

SCOPES = ("attention", "mlp", "vocab", "optimizer")
IDLE = {"idle_feed_ms": ("train.feed",),
        "idle_readback_ms": ("train.readback",),
        "idle_loop_ms": ("train.prepare", "train.dispatch", "train.control")}
HISTORY_SPANS = ("feed_s", "dispatch_s", "readback_s", "control_s")


def step_hlo(session, params, opt_state) -> str:
    """Optimized HLO text of the session's compiled step, lowered with the
    window's shapes and shardings (the persistent cache holds it)."""
    import jax

    from repro.train.steps import abstract_batch

    compiled = session.compile()
    args = (params, opt_state,
            abstract_batch(compiled.global_rows, compiled.seq_len))
    shapes = jax.tree_util.tree_map(
        lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
        args, compiled.in_shardings)
    return compiled.step_fn.lower(*shapes).compile().as_text()


@contextlib.contextmanager
def instrumented(seen: dict):
    """Let the harness run as it is, and keep on the side: the step's HLO
    text (after set-up), each window call's readbacks, steps and span
    seconds, and the raw trace the harness reduces."""
    setup, reduce_dir = H.setup_program, TR.reduce_dir
    seen.update(calls=[], trace=None, hlo="")

    def setup_program(*args, **kwargs):
        out = setup(*args, **kwargs)
        session, params, opt_state = out[:3]
        seen["hlo"] = step_hlo(session, params, opt_state)
        session_run = session.run

        def counted(*a, **k):
            t = time.perf_counter()
            rep = session_run(*a, **k)
            spans = {s: sum(h.get(s, 0.0) for h in rep.history)
                     for s in HISTORY_SPANS}
            seen["calls"].append({
                "seconds": time.perf_counter() - t,
                "steps": rep.steps_run,
                "readbacks": getattr(rep, "readbacks", None),
                "spans": spans,
            })
            return rep

        session.run = counted
        return out

    def reduce_and_keep(directory, n_devices=None):
        seen["trace"] = TR.load(TR.find_trace(directory))
        seen["n_devices"] = n_devices
        return TR.reduce(seen["trace"], n_devices)

    H.setup_program, TR.reduce_dir = setup_program, reduce_and_keep
    try:
        yield seen
    finally:
        H.setup_program, TR.reduce_dir = setup, reduce_dir


def split(seen: dict) -> dict:
    """The per-step numbers of the module docstring."""
    trace, calls = seen["trace"], seen["calls"]
    steps = sum(c["steps"] for c in calls)
    ms = 1e-6 / steps          # ns in the window -> ms per step
    idle = TS.idle_by_span(trace, [n for ns in IDLE.values() for n in ns])
    out = {k: ms * sum(idle[n] for n in ns) for k, ns in IDLE.items()}
    out["idle_other_ms"] = ms * idle[TS.NONE]
    out["idle_ms"] = ms * sum(idle.values())
    per_dev = TS.scope_self_times(trace, TS.op_scopes(seen["hlo"], SCOPES),
                                  seen["n_devices"])
    n = max(len(per_dev), 1)
    for scope in SCOPES + (TS.NONE,):
        name = f"{scope or 'other'}_ms"
        out[name] = ms * sum(d.get(scope, 0) for d in per_dev.values()) / n
    out["busy_ms"] = ms * sum(sum(d.values()) for d in per_dev.values()) / n
    readbacks = [c["readbacks"] for c in calls]
    out["readbacks_per_step"] = (None if None in readbacks
                                 else sum(readbacks) / steps)
    out["steps"] = steps
    return out


def main(argv=None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    seen: dict = {}
    with instrumented(seen):
        rc = run.main(argv + ["--trace", "1"])
    if rc:
        return rc
    longest = max(seen["calls"], key=lambda c: c["seconds"])
    print(f"longest call {longest['seconds']:.4f} s, {longest['steps']} "
          f"steps; seconds per span: "
          + ", ".join(f"{k} {v:.4f}" for k, v in longest["spans"].items()),
          file=sys.stderr)
    print(json.dumps({"layer_split": split(seen)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
