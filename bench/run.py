"""Run one cell of the chip benchmark.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Finds the cell in ``BENCHMARK.json`` and its files under ``bench/``, refuses
to run without as many TPU chips as the cell asks for (exit 3, no result),
keeps JAX's compilation cache in ``<checkout>/.jax_cache``, and prints the
result as one JSON object on the last line of standard output.  The numbers
of the correctness check, each beside its limit, are the last lines of
standard error and the last key of the result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
# the cache lives in the checkout, at a path that never moves; the program
# takes the directory from this variable
os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
# JAX does not create the directory; without it every entry fails to write
os.makedirs(ROOT / ".jax_cache", exist_ok=True)
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from bench.harness import find_cell, run_cell

    cell = find_cell(args.workload, ROOT)
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"bench: {cell.name} needs {cell.chips} TPU chip(s); JAX found "
              f"{len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return 3
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    from repro.launch.compile_cache import configure_compile_cache

    configure_compile_cache()

    result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                      t_start=T_START, devices=devices[: cell.chips])
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
