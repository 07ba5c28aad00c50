"""Record the scoped profiler trace that ``test_trace_scopes.py`` reads.

    python bench/tests/record_scoped_trace.py [OUT_DIR]

Run on a TPU host.  It jits the gradient of a small loss whose two matrix
products sit in the named scopes ``attention`` and ``mlp``, runs it five
times with a 20 ms host sleep inside a ``train.feed`` span before each call
(so the device has idle gaps under a program span), traces those calls, and
writes beside ``small.xplane.pb``:

* ``scoped.xplane.pb``: the trace;
* ``scoped.hlo.txt``: the optimized HLO text of the same jitted step.

The checkout's path is replaced, byte for byte, by a placeholder of the
same length in both files (nothing reads it).  It prints each device op of
the trace that the HLO text does not name (there should be none).
"""
from __future__ import annotations

import glob
import os
import re
import sys
import tempfile
import time
from pathlib import Path

import jax
import jax.numpy as jnp

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]


def _scrub(data: bytes) -> bytes:
    root = str(ROOT).encode()
    return data.replace(root, (b"/checkout" + b"0" * len(root))[: len(root)])


def main() -> int:
    if jax.devices()[0].platform != "tpu":
        print("record_scoped_trace: needs a TPU", file=sys.stderr)
        return 2
    out = Path(sys.argv[1]) if len(sys.argv) > 1 else HERE / "data"
    out.mkdir(parents=True, exist_ok=True)

    def loss(w, x):
        with jax.named_scope("attention"):
            h = jnp.tanh(x @ w)
        with jax.named_scope("mlp"):
            h = jnp.tanh(h @ w)
        return jnp.mean(h)

    step = jax.jit(jax.grad(loss))
    x = jnp.ones((2048, 2048), jnp.bfloat16)
    w = jnp.full((2048, 2048), 1e-3, jnp.bfloat16)
    step(w, x).block_until_ready()
    hlo = step.lower(w, x).compile().as_text()
    tmp = tempfile.mkdtemp()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(tmp, profiler_options=opts)
    for _ in range(5):
        with jax.profiler.TraceAnnotation("train.feed"):
            time.sleep(0.02)
        with jax.profiler.TraceAnnotation("bench.run"):
            g = step(w, x)
            g.block_until_ready()
    jax.profiler.stop_trace()
    path = sorted(glob.glob(os.path.join(tmp, "**", "*.xplane.pb"),
                            recursive=True))[-1]
    (out / "scoped.xplane.pb").write_bytes(_scrub(Path(path).read_bytes()))
    (out / "scoped.hlo.txt").write_bytes(_scrub(hlo.encode()))

    sys.path[:0] = [str(ROOT)]
    from bench import trace_reduce as TR
    from bench import trace_scopes as TS

    trace = TR.load(str(out / "scoped.xplane.pb"))
    scopes = TS.op_scopes(hlo, ("attention", "mlp"))
    names = set(re.findall(r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = ", hlo,
                           re.MULTILINE))
    for dev, ops in sorted(trace.devices.items()):
        seen = {nm.lstrip("%") for nm, _, _ in ops}
        print(f"{dev}: {len(ops)} ops; not in the HLO text: "
              f"{sorted(seen - names)}; scopes: "
              f"{sorted((nm, scopes.get(nm)) for nm in seen)}")
    print(f"self times: {TS.scope_self_times(trace, scopes)}")
    print(f"idle: {TS.idle_by_span(trace, ('train.feed',))}")
    for d in ("scoped.xplane.pb", "scoped.hlo.txt"):
        print(f"{d}: {os.path.getsize(out / d)} bytes")
    return 0


if __name__ == "__main__":
    sys.exit(main())
