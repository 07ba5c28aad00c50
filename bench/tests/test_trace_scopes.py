"""The program's layers in a trace: ops mapped to named scopes through the
optimized HLO text, device self time per scope, and device idle time split
among the program's host spans.  On hand-made intervals with exact
answers, and on a small scoped trace recorded on a TPU v5e with its HLO
text (``data/scoped.xplane.pb``, ``data/scoped.hlo.txt``, made by
``record_scoped_trace.py``)."""
import re
from pathlib import Path

import pytest

from bench import trace_reduce as TR
from bench import trace_scopes as TS

DATA = Path(__file__).resolve().parent / "data"
SCOPES = ("attention", "mlp", "vocab", "optimizer")

HLO = """HloModule jit_step, is_scheduled=true

ENTRY %main.9 (p0: f32[8]) -> (f32[8]) {
  %p0 = f32[8]{0} parameter(0), metadata={op_name="params[0]"}
  %fusion.1 = f32[8]{0} fusion(f32[8]{0} %p0), kind=kLoop, calls=%fc.1, metadata={op_name="jit(step)/jvp()/while/body/closed_call/attention/dot_general" stack_frame_id=3}
  %fusion.2 = f32[8]{0} fusion(%fusion.1), kind=kOutput, calls=%fc.2, metadata={op_name="jit(step)/transpose(jvp(attention))/dot_general"}
  %fusion.3 = f32[8]{0} fusion(%fusion.2), kind=kLoop, calls=%fc.3, metadata={op_name="jit(step)/transpose(jvp())/while/body/closed_call/checkpoint/mlp/mul"}
  %fusion.4 = f32[8]{0} fusion(%fusion.3), kind=kLoop, calls=%fc.4, metadata={op_name="jit(step)/optimizer/sqrt"}
  %fusion.5 = f32[8]{0} fusion(%fusion.4), kind=kLoop, calls=%fc.5, metadata={op_name="jit(step)/vocab/attention/exp"}
  %copy.6 = f32[8]{0} copy(%fusion.5), metadata={op_name="jit(step)/jvp()/while"}
  %while.7 = f32[8]{0} while(%copy.6), condition=%c, body=%b
  ROOT %tuple.8 = (f32[8]{0}) tuple(%while.7)
}
"""


def test_op_scopes_innermost_scope_through_wrappers():
    assert TS.op_scopes(HLO, SCOPES) == {
        "fusion.1": "attention",
        # backward: the scope sits inside transpose(jvp(...))
        "fusion.2": "attention",
        # rematerialized backward of a scanned block
        "fusion.3": "mlp",
        "fusion.4": "optimizer",
        # nested scopes: the innermost wins
        "fusion.5": "attention",
    }
    assert TS.op_scopes(HLO, ("mlp",)) == {"fusion.3": "mlp"}


def _trace():
    # window 0..150 ns from the bench spans; a loop (while.7) holds two ops
    d0 = [("%while.7", 0, 100), ("%fusion.1", 10, 40), ("%fusion.3", 50, 80),
          ("%fusion.4", 100, 120)]
    # fusion.2 in the window, and fusion.4 partly after it
    d1 = [("%fusion.2", 5, 25), ("%fusion.4", 140, 170)]
    host = [("bench.run", 0, 150, 0)]
    return TR.Trace(devices={"/device:TPU:0": d0, "/device:TPU:1": d1},
                    host=host)


def test_scope_self_times_per_device():
    scopes = TS.op_scopes(HLO, SCOPES)
    got = TS.scope_self_times(_trace(), scopes)
    assert got == {
        # the loop keeps 100 - 30 - 30 ns of its own, in no scope
        "/device:TPU:0": {TS.NONE: 40, "attention": 30, "mlp": 30,
                          "optimizer": 20},
        "/device:TPU:1": {"attention": 20, "optimizer": 10},
    }
    assert list(TS.scope_self_times(_trace(), scopes, n_devices=1)) == [
        "/device:TPU:0"]


def _idle_trace():
    # device 0 busy 10..30 and 50..60; idle 0..10, 30..50, 60..100
    d0 = [("%fusion.1", 10, 30), ("%fusion.2", 50, 60)]
    host = [("bench.run", 0, 100, 0),
            ("train.prepare", 0, 5, 0), ("train.feed", 5, 20, 0),
            ("train.dispatch", 20, 35, 0), ("train.readback", 35, 55, 0),
            ("train.control", 55, 70, 0),
            # another thread's span does not count
            ("train.feed", 70, 90, 1)]
    return TR.Trace(devices={"/device:TPU:0": d0}, host=host)


def test_idle_split_among_spans():
    names = ("train.prepare", "train.feed", "train.dispatch",
             "train.readback", "train.control")
    got = TS.idle_by_span(_idle_trace(), names)
    assert got == {
        # the gap 0..10 split across two spans
        "train.prepare": 5, "train.feed": 5,
        # the gap 30..50 split across two spans
        "train.dispatch": 5, "train.readback": 15,
        "train.control": 10,
        # 70..100 lies under no span of the driving thread
        TS.NONE: 30,
    }
    assert sum(got.values()) == 70


def test_idle_split_first_name_wins_and_empty_trace():
    tr = _idle_trace()
    tr.host.append(("train.step", 0, 100, 0))
    got = TS.idle_by_span(tr, ("train.feed", "train.step"))
    assert got == {"train.feed": 5, "train.step": 65, TS.NONE: 0}
    assert TS.idle_by_span(TR.Trace(devices={}, host=[]), ("train.feed",)) \
        == {"train.feed": 0, TS.NONE: 0}


@pytest.mark.skipif(not (DATA / "scoped.xplane.pb").exists(),
                    reason="no recorded scoped trace")
def test_recorded_scoped_tpu_trace():
    """Five calls of a jitted gradient whose two products sit in the scopes
    ``attention`` and ``mlp``, each after a 20 ms sleep in ``train.feed``:
    every device op is an instruction of the HLO text, both scopes hold
    device time, and the idle of the four feeds inside the window (the
    first precedes the first ``bench.run``) is under the feed span."""
    trace = TR.load(str(DATA / "scoped.xplane.pb"))
    hlo = (DATA / "scoped.hlo.txt").read_text()
    names = set(re.findall(r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = ", hlo,
                           re.MULTILINE))
    (dev,) = trace.devices
    ops = {nm.lstrip("%") for nm, _, _ in trace.devices[dev]}
    assert ops and ops <= names
    scopes = TS.op_scopes(hlo, ("attention", "mlp"))
    times = TS.scope_self_times(trace, scopes)[dev]
    assert times["attention"] > 0 and times["mlp"] > 0
    idle = TS.idle_by_span(trace, ("train.feed",))
    assert idle["train.feed"] >= 4 * 0.02e9 * 0.9
    assert idle["train.feed"] > 0.9 * sum(idle.values())
