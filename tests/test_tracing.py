"""The program's own observability of training.

``Session.run`` wraps each part of its step loop in a host span
(``train.prepare`` once, then ``train.feed``, ``train.dispatch``,
``train.readback`` and ``train.control`` per step) that lands in a profiler
trace and in each step's ``history`` entry; the train step names its ops
with the scopes ``attention``, ``mlp``, ``vocab`` and ``optimizer``, which
change the compiled program's metadata and nothing else."""
import contextlib
import glob
import os
import re

import jax
import pytest

from repro.api import FleetSpec, Session, SessionConfig
from repro.configs import smoke_config
from repro.models.api import get_model
from repro.optim import adamw
from repro.storage import DataConfig

STEPS = 2
LOOP = ("train.feed", "train.dispatch", "train.readback", "train.control")
SCOPES = ("attention", "mlp", "vocab", "optimizer")


def _session():
    cfg = smoke_config("deepseek-7b")
    spec = FleetSpec.demo(2)
    return Session(
        model=get_model(cfg),
        optimizer=adamw(),
        fleet=spec,
        data=DataConfig(vocab=cfg.vocab, seq_len=16),
        shards=spec.shards(private_per_worker={"csd": 64}, public=4096),
        config=SessionConfig(total_steps=4),
    )


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """A session whose ``run(steps=2)`` was traced by the profiler, with its
    stages built inside the trace but outside the run; returns the session,
    the report and the host events ``(name, start, end, thread)``."""
    session = _session()
    out = tmp_path_factory.mktemp("trace")
    jax.profiler.start_trace(str(out))
    try:
        session.compile()
        session.dataset
        with jax.profiler.TraceAnnotation("test.run"):
            report = session.run(steps=STEPS)
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(out, "**", "*.xplane.pb"),
                        recursive=True)
    events = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for i, line in enumerate(plane.lines):
                events += [(e.name, e.start_ns, e.start_ns + e.duration_ns,
                            (plane.name, i)) for e in line.events]
    return session, report, events


def test_run_spans_in_loop_order_on_one_thread(traced):
    _, _, events = traced
    train = sorted((e for e in events if e[0].startswith("train.")),
                   key=lambda e: e[1])
    assert [e[0] for e in train] == ["train.prepare"] + list(LOOP) * STEPS
    assert len({e[3] for e in train}) == 1
    # one after another, never nested in each other
    assert all(a[2] <= b[1] for a, b in zip(train, train[1:]))
    # all of them inside the run: the stages built before it have none
    (run,) = [e for e in events if e[0] == "test.run"]
    assert all(run[1] <= e[1] and e[2] <= run[2] for e in train)


def test_readbacks_and_span_seconds_in_history(traced):
    _, report, _ = traced
    assert report.steps_run == STEPS
    # the float() of each of the step's six metrics
    assert report.readbacks == 6 * report.steps_run
    for h in report.history:
        for key in ("feed_s", "dispatch_s", "readback_s", "control_s"):
            assert h[key] >= 0.0
        assert h["step_time"] >= h["dispatch_s"] + h["readback_s"]


def _step_hlo(session, report):
    """Optimized HLO text of the session's compiled train step."""
    compiled = session.compile()
    batch = session.dataset.next_device_batch()
    return compiled.step_fn.lower(
        report.params, report.opt_state, batch).compile().as_text()


def _strip_metadata(text):
    """The HLO text without op metadata and without the tables of source
    locations it points into (they differ with the caller's stack)."""
    text = re.sub(r"^(FileNames|FunctionNames|FileLocations|StackFrames)\n"
                  r"(.+\n)*\n?", "", text, flags=re.MULTILINE)
    return re.sub(r",? metadata=\{[^}]*\}", "", text)


def test_scopes_name_ops_and_change_only_metadata(traced, monkeypatch):
    session, report, _ = traced
    scoped = _step_hlo(session, report)
    op_names = re.findall(r'op_name="([^"]*)"', scoped)
    for scope in SCOPES:
        assert any(re.search(rf"(^|[/(]){scope}([/)]|$)", n)
                   for n in op_names), scope
    # the same step traced anew with every named scope a no-op
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    plain = _step_hlo(_session(), report)
    assert not any(re.search(rf"[/(]{s}[/)]", n) for s in SCOPES
                   for n in re.findall(r'op_name="([^"]*)"', plain))
    assert _strip_metadata(plain) == _strip_metadata(scoped)
