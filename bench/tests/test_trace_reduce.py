"""The reduction from a profiler trace to busy and idle time, exposed
collective time, top operations and labelled idle gaps: on hand-made
intervals, and on a small trace recorded on a TPU v5e
(``data/small.xplane.pb``, made by ``record_trace.py``)."""
from pathlib import Path

import pytest

from bench import trace_reduce as TR

DATA = Path(__file__).resolve().parent / "data" / "small.xplane.pb"


def _trace():
    # two devices, window 0..100 ns bracketed by bench spans
    d0 = [("fusion.1", 10, 30), ("all-gather-start", 30, 40),
          ("fusion.2", 35, 50), ("all-reduce.3", 60, 70)]
    d1 = [("fusion.1", 10, 40), ("reduce-scatter.1", 40, 60)]
    host = [("bench.run", 0, 100, 0), ("bench.feed", 50, 60, 0),
            ("PjitFunction(step)", 75, 95, 0)]
    return TR.Trace(devices={"/device:TPU:0": d0, "/device:TPU:1": d1},
                    host=host)


def test_interval_helpers():
    assert TR.union([(5, 9), (0, 3), (2, 4)]) == [(0, 4), (5, 9)]
    # a loop (0..10) holding two ops keeps only its own 4 ns
    assert TR.self_times([("while", 0, 10), ("a", 1, 4), ("b", 5, 8)]) == [
        ("while", 4), ("a", 3), ("b", 3)]
    assert TR.subtract([(0, 10)], [(2, 3), (5, 12)]) == [(0, 2), (3, 5)]
    assert TR.clip([(0, 10), (20, 30)], 5, 25) == [(5, 10), (20, 25)]


def test_busy_collectives_and_gaps():
    s = TR.reduce(_trace())
    assert s.window_s == pytest.approx(100e-9)
    # device 0 busy 10..50 and 60..70 = 50; device 1 busy 10..60 = 50
    assert s.busy_s == pytest.approx(50e-9)
    # collectives: d0 30..40 and 60..70 (20), d1 40..60 (20)
    assert s.collective_s == pytest.approx(20e-9)
    # exposed: d0 30..35 and 60..70 (15), d1 40..60 (20) -> mean 17.5
    assert s.collective_exposed_s == pytest.approx(17.5e-9)
    assert s.top_ops[0] == ["fusion.1", pytest.approx(25e-9)]
    # device 0 gaps: 0..10, 50..60 (in bench.feed), 70..100 (in the
    # program's dispatch span)
    labels = {lab: sec for lab, sec in s.idle_gaps}
    assert labels["bench.feed"] == pytest.approx(10e-9)
    assert labels["PjitFunction(step)"] == pytest.approx(30e-9)
    assert s.idle_gaps[0][1] >= s.idle_gaps[-1][1]


@pytest.mark.skipif(not DATA.exists(), reason="no recorded trace")
def test_recorded_tpu_trace():
    """Five calls of a jitted matmul chain, each after a 20 ms host sleep
    inside a ``bench.feed`` span: the device idles at least 5 x 20 ms, and
    the longest gaps are labelled with the feed span."""
    s = TR.reduce(TR.load(str(DATA)))
    assert s.n_devices == 1
    assert 0 < s.busy_s < s.window_s
    assert s.window_s - s.busy_s >= 5 * 0.02 * 0.9
    assert s.collective_s == 0
    assert s.top_ops and s.top_ops[0][1] > 0
    assert s.idle_gaps[0][0] == "bench.feed"
    assert s.idle_gaps[0][1] >= 0.018
