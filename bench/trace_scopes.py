"""From a profiler trace to the program's own layers.

``trace_reduce`` says how much of the window the device was busy or idle.
This module says whose time it was, from what the program puts into its
trace and its compiled step:

* ``op_scopes``: each instruction of the optimized HLO text mapped to the
  innermost named scope (``jax.named_scope``) found in its ``op_name``;
  backward and rematerialized ops carry their scope inside wrappers such as
  ``transpose(jvp(attention))`` or ``checkpoint/attention``;
* ``scope_self_times``: each device's op self time in the window, summed by
  scope;
* ``idle_by_span``: the first device's idle time in the window, split among
  the host spans (``train.feed``, ...) that cover each part of it on the
  thread that drives the window.
"""
from __future__ import annotations

import re
from typing import Dict, List, Optional, Sequence, Tuple

from bench import trace_reduce as TR

# key of the time that falls under none of the given scopes or spans
NONE = ""

# "  %fusion.12 = bf16[..] fusion(...), ..., metadata={op_name="a/b" ...}"
_INSTRUCTION = re.compile(
    r'^\s*(?:ROOT\s+)?%?([\w.\-]+) = [^\n]*?metadata=\{[^}\n]*?'
    r'op_name="([^"]*)"', re.MULTILINE)


def op_scopes(hlo_text: str, scopes: Sequence[str]) -> Dict[str, str]:
    """Instruction name (without ``%``) -> the innermost of ``scopes`` in
    its ``op_name``; instructions in none of them are left out."""
    wanted = set(scopes)
    out: Dict[str, str] = {}
    for name, op_name in _INSTRUCTION.findall(hlo_text):
        for part in reversed(op_name.split("/")):
            # "transpose(jvp(attention))": the innermost word is last
            hits = [w for w in re.split(r"[()]", part) if w in wanted]
            if hits:
                out[name] = hits[-1]
                break
    return out


def window(trace: TR.Trace) -> Tuple[int, int]:
    """The window ``trace_reduce.reduce`` measures: from the first to the
    last harness span (``bench.*``), else the device ops' extent."""
    bench = [h for h in trace.host if h[0].startswith(TR.BENCH_SPAN)]
    if bench:
        return min(h[1] for h in bench), max(h[2] for h in bench)
    ops = [iv for ivs in trace.devices.values() for iv in ivs]
    if not ops:
        return 0, 0
    return min(s for _, s, _ in ops), max(e for _, _, e in ops)


def scope_self_times(trace: TR.Trace, op_scope: Dict[str, str],
                     n_devices: Optional[int] = None
                     ) -> Dict[str, Dict[str, int]]:
    """Device plane -> scope -> ns of op self time (``self_times``) inside
    the window; ops in no scope are summed under ``NONE``."""
    lo, hi = window(trace)
    out: Dict[str, Dict[str, int]] = {}
    for dev in sorted(trace.devices)[: n_devices or None]:
        per: Dict[str, int] = {}
        for nm, own in TR.self_times(TR.clip_ops(trace.devices[dev], lo, hi)):
            key = op_scope.get(nm.lstrip("%"), NONE)
            per[key] = per.get(key, 0) + own
        out[dev] = per
    return out


def _intersect(a: List[Tuple[int, int]], b) -> List[Tuple[int, int]]:
    return TR.subtract(a, TR.subtract(a, b))


def idle_by_span(trace: TR.Trace, names: Sequence[str]) -> Dict[str, int]:
    """The first device's idle ns inside the window, split among the host
    spans called ``names`` on the thread that drives the window (the one
    with the ``bench.*`` spans; any thread if there are none).  A part
    covered by spans of two names goes to the one named first; idle time
    under none of them is under ``NONE``.  The parts add up to the idle
    time."""
    lo, hi = window(trace)
    out = {n: 0 for n in names}
    out[NONE] = 0
    if not trace.devices or hi <= lo:
        return out
    ops = trace.devices[sorted(trace.devices)[0]]
    busy = TR.clip(TR.union([(s, e) for _, s, e in ops]), lo, hi)
    rest = TR.subtract([(lo, hi)], busy)
    lines = {h[3] for h in trace.host if h[0].startswith(TR.BENCH_SPAN)}
    for n in names:
        spans = [(s, e) for nm, s, e, line in trace.host
                 if nm == n and (not lines or line in lines)]
        out[n] = TR.total(_intersect(rest, TR.union(spans)))
        rest = TR.subtract(rest, spans)
    out[NONE] = TR.total(rest)
    return out
