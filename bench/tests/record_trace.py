"""Record the small profiler trace that ``test_trace_reduce.py`` reads.

    python bench/tests/record_trace.py [OUT_DIR]

Run on a TPU host.  It jits a short chain of matrix products, runs it five
times with a host span ``bench.feed`` and a 20 ms host sleep before each
call (so the device has idle gaps the reduction must label), traces those
calls, copies the ``.xplane.pb`` to ``bench/tests/data/small.xplane.pb``
and prints every plane and line of the trace with its event count and a
few event names.
"""
from __future__ import annotations

import glob
import os
import shutil
import sys
import tempfile
import time
from pathlib import Path

import jax
import jax.numpy as jnp

HERE = Path(__file__).resolve().parent


def main() -> int:
    if jax.devices()[0].platform != "tpu":
        print("record_trace: needs a TPU", file=sys.stderr)
        return 2
    out = Path(sys.argv[1]) if len(sys.argv) > 1 else HERE / "data"
    out.mkdir(parents=True, exist_ok=True)

    @jax.jit
    def chain(x, w):
        for _ in range(4):
            x = jnp.tanh(x @ w)
        return x

    x = jnp.ones((2048, 2048), jnp.bfloat16)
    w = jnp.full((2048, 2048), 1e-3, jnp.bfloat16)
    chain(x, w).block_until_ready()
    tmp = tempfile.mkdtemp()
    jax.profiler.start_trace(tmp)
    for _ in range(5):
        with jax.profiler.TraceAnnotation("bench.feed"):
            time.sleep(0.02)
        with jax.profiler.TraceAnnotation("bench.run"):
            x = chain(x, w)
            x.block_until_ready()
    jax.profiler.stop_trace()
    path = sorted(glob.glob(os.path.join(tmp, "**", "*.xplane.pb"),
                            recursive=True))[-1]
    shutil.copy(path, out / "small.xplane.pb")
    shutil.rmtree(tmp, ignore_errors=True)

    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(out / "small.xplane.pb"))
    for plane in pd.planes:
        lines = list(plane.lines)
        print(f"plane {plane.name!r}: {len(lines)} lines")
        for line in lines:
            evs = list(line.events)
            names = sorted({e.name for e in evs})[:12]
            span = ""
            if evs:
                span = (f" t0={evs[0].start_ns} "
                        f"t1={evs[-1].start_ns + evs[-1].duration_ns}")
            print(f"  line {line.name!r}: {len(evs)} events{span} {names}")
    print(f"bytes: {os.path.getsize(out / 'small.xplane.pb')}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
