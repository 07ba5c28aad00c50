"""Readings that the correctness limits of a cell are set from.

    python3 bench/calibrate.py --workload <name> --seeds 1,2,3 \
        [--control-seeds 1,2,3] [--out FILE]

For every seed: the program's first steps through ``Session.run`` (as a run
of the cell makes them, without the window), then the f32 reference, and
the gaps between them: the lower readings.  For every control seed also:
the control (the reference with int8 matrix products) and the planted
faults of the cell, each against the reference: the upper readings.  A
step that returns its state unchanged reads 1 in ``change_gap`` by
construction and needs no run.  The benchmark's own runs never run this.
Each seed's readings are printed, and appended to ``--out``, as one JSON
line.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
# JAX does not create the directory; without it every entry fails to write
os.makedirs(ROOT / ".jax_cache", exist_ok=True)
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def gaps(prog, ref_out):
    from bench.harness import gaps as harness_gaps

    return harness_gaps(prog["losses"], prog["grad_norms"],
                        prog["change_norms"], ref_out)


def faults(cell):
    """(name, reference keyword arguments) of each planted fault that the
    cell can have and that needs a run."""
    out = [("half_batch", {"fraction": 0.5})]
    if cell.chips > 1:
        # no exchange between chips: the update sees chip 0's rows only
        out.append(("no_exchange", {"fraction": 1.0 / cell.chips}))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    import jax

    from bench import harness as H

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    from repro.launch.compile_cache import configure_compile_cache

    configure_compile_cache()
    cell = H.find_cell(args.workload, ROOT)
    devices = jax.devices()[: cell.chips]
    ref = H.reference_module(cell.config["family"], ROOT)
    seeds = [int(s) for s in args.seeds.split(",") if s]
    control = {int(s) for s in args.control_seeds.split(",") if s}
    for seed in sorted(set(seeds) | control):
        line = {"workload": cell.name, "seed": seed}
        t = time.perf_counter()
        spool = None
        if cell.traffic["storage"] == "flash":
            import tempfile

            spool = tempfile.mkdtemp(prefix="bench-spool-")
        session, params, opt, feed, rd = H.setup_program(
            cell, seed, devices, spool)
        line["program_s"] = time.perf_counter() - t
        del session, params, opt, feed
        H.free_device_state()
        if spool:
            import shutil

            shutil.rmtree(spool, ignore_errors=True)
        t = time.perf_counter()
        base = ref.train_readings(cell.config, cell.traffic, seed,
                                  devices=devices)
        line["reference_s"] = time.perf_counter() - t
        line["feed_rows_wrong"] = H.feed_mismatches(cell, ref, seed,
                                                    rd.batches)
        line["program"] = gaps({"losses": rd.losses,
                                "grad_norms": rd.grad_norms,
                                "change_norms": rd.change_norms}, base)
        line["losses"] = {"program": rd.losses, "reference": base["losses"]}
        if seed in control:
            t = time.perf_counter()
            ctl = ref.train_readings(cell.config, cell.traffic, seed,
                                     precision="int8", devices=devices)
            line["control_s"] = time.perf_counter() - t
            line["control"] = gaps(ctl, base)
            for name, kw in faults(cell):
                out = ref.train_readings(cell.config, cell.traffic, seed,
                                         devices=devices, **kw)
                line[name] = gaps(out, base)
        H.free_device_state()
        print(json.dumps(line), flush=True)
        if args.out:
            os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
            with open(args.out, "a") as f:
                f.write(json.dumps(line) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
