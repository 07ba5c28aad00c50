"""From a profiler trace (``.xplane.pb``) to the benchmark's device numbers.

Reads the trace with nothing but JAX (``jax.profiler.ProfileData``) into
plain intervals, then reduces them:

* busy seconds: the union of the intervals in which an operation ran on a
  device (its ``XLA Ops`` line), averaged over the devices;
* the window: from the first to the last host span the harness wrote
  (``bench.*``), which bracket the measured calls;
* exposed collective seconds: time in which a collective operation runs on
  a device and no other operation does, summed, averaged over devices;
* the device operations that took most time of their own (averaged over
  devices; a loop's time less that of the ops inside it);
* the longest idle gaps of the first device, each labelled with the host
  span it falls in: the harness's own spans (``bench.feed``, ...) first,
  else the innermost span of the host's threads, else ``host``.
"""
from __future__ import annotations

import dataclasses
import glob
import os
from typing import Dict, List, Optional, Sequence, Tuple

Interval = Tuple[str, int, int]          # (name, start ns, end ns)

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
BENCH_SPAN = "bench."
RUN_SPAN = "bench.run"
COLLECTIVE_WORDS = ("all-gather", "all-reduce", "reduce-scatter",
                    "collective-permute", "all-to-all", "allgather",
                    "allreduce", "reducescatter", "send", "recv")
TOP_N = 10


@dataclasses.dataclass
class Trace:
    devices: Dict[str, List[Interval]]       # plane name -> device ops
    host: List[Tuple[str, int, int, int]]    # (name, start, end, host line)


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float
    collective_s: float
    collective_exposed_s: float
    n_devices: int
    top_ops: List[List]
    idle_gaps: List[List]


def load(path: str) -> Trace:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    devices: Dict[str, List[Interval]] = {}
    host: List[Tuple[str, int, int, int]] = []
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            lines = {line.name: line for line in plane.lines}
            line = lines.get(OPS_LINE) or next(
                (v for k, v in lines.items() if "Ops" in k), None)
            if line is not None:
                # an op's event name is its whole HLO instruction; keep
                # the instruction's name ("%fusion.12")
                devices[plane.name] = sorted(
                    (e.name.split(" = ", 1)[0], int(e.start_ns),
                     int(e.start_ns + e.duration_ns))
                    for e in line.events
                )
        elif plane.name == HOST_PLANE:
            for i, line in enumerate(plane.lines):
                for e in line.events:
                    host.append((e.name, int(e.start_ns),
                                 int(e.start_ns + e.duration_ns), i))
    return Trace(devices=devices, host=host)


def union(intervals: Sequence[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[List[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo: int, hi: int):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def total(intervals) -> int:
    return sum(e - s for s, e in intervals)


def subtract(a, b) -> List[Tuple[int, int]]:
    """Parts of the (disjoint, sorted) intervals ``a`` not covered by ``b``."""
    out = []
    b = union(b)
    j = 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def is_collective(name: str) -> bool:
    n = name.lower()
    return any(w in n for w in COLLECTIVE_WORDS)


def clip_ops(ops: Sequence[Interval], lo: int, hi: int) -> List[Interval]:
    return [(nm, max(s, lo), min(e, hi)) for nm, s, e in ops
            if min(e, hi) > max(s, lo)]


def self_times(ops: Sequence[Interval]) -> List[Tuple[str, int]]:
    """(name, own time) of each op: its span less the spans of the ops
    nested in it (a loop's body ops run inside the loop's own event)."""
    out: List[List] = []
    stack: List[int] = []
    for nm, s, e in sorted(ops, key=lambda o: (o[1], -o[2])):
        while stack and out[stack[-1]][2] <= s:
            stack.pop()
        if stack:
            out[stack[-1]][3] -= min(e, out[stack[-1]][2]) - s
        out.append([nm, s, e, e - s])
        stack.append(len(out) - 1)
    return [(nm, own) for nm, _, _, own in out]


def _label(gap: Tuple[int, int], host) -> str:
    """What the host thread that drives the window was doing mid-gap: a
    harness span inside a call (``bench.feed``), else the innermost span
    of that thread (the program's own), else the call itself."""
    mid = (gap[0] + gap[1]) // 2
    lines = {h[3] for h in host if h[0].startswith(BENCH_SPAN)}
    covering = [h for h in host if h[1] <= mid < h[2]
                and (not lines or h[3] in lines)]
    inner = [h for h in covering if h[0] != RUN_SPAN]
    bench = [h for h in inner if h[0].startswith(BENCH_SPAN)]
    pick = bench or inner or covering
    if not pick:
        return "host"
    # innermost: the latest-starting span that covers the midpoint
    return max(pick, key=lambda h: h[1])[0]


def reduce(trace: Trace, n_devices: Optional[int] = None) -> Summary:
    names = sorted(trace.devices)[: n_devices or None]
    bench = [h for h in trace.host if h[0].startswith(BENCH_SPAN)]
    spans = bench or trace.host
    all_ops = [iv for n in names for iv in trace.devices[n]]
    if spans:
        lo, hi = min(h[1] for h in spans), max(h[2] for h in spans)
    elif all_ops:
        lo, hi = min(s for _, s, _ in all_ops), max(e for _, _, e in all_ops)
    else:
        lo = hi = 0
    window = max(hi - lo, 0)
    busy, coll, exposed = [], [], []
    per_op: Dict[str, int] = {}
    for n in names:
        ops = trace.devices[n]
        busy_iv = clip(union([(s, e) for _, s, e in ops]), lo, hi)
        busy.append(total(busy_iv))
        c = clip(union([(s, e) for nm, s, e in ops if is_collective(nm)]),
                 lo, hi)
        other = clip(union([(s, e) for nm, s, e in ops
                            if not is_collective(nm)]), lo, hi)
        coll.append(total(c))
        exposed.append(total(subtract(c, other)))
        for nm, d in self_times(clip_ops(ops, lo, hi)):
            per_op[nm] = per_op.get(nm, 0) + d
    nd = max(len(names), 1)
    top = sorted(per_op.items(), key=lambda kv: -kv[1])[:TOP_N]
    gaps: List[Tuple[int, int]] = []
    if names:
        busy0 = clip(union([(s, e) for _, s, e in trace.devices[names[0]]]),
                     lo, hi)
        gaps = subtract([(lo, hi)], busy0) if window else []
    gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:TOP_N]
    return Summary(
        window_s=window / 1e9,
        busy_s=sum(busy) / nd / 1e9,
        collective_s=sum(coll) / nd / 1e9,
        collective_exposed_s=sum(exposed) / nd / 1e9,
        n_devices=len(names),
        top_ops=[[nm, t / nd / 1e9] for nm, t in top],
        idle_gaps=[[_label(g, trace.host), (g[1] - g[0]) / 1e9] for g in gaps],
    )


def find_trace(directory: str) -> str:
    paths = sorted(glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return paths[-1]


def reduce_dir(directory: str, n_devices: Optional[int] = None) -> Summary:
    return reduce(load(find_trace(directory)), n_devices)
