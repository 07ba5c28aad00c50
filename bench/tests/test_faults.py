"""A run of the harness, with its look for a chip skipped, sees ``correct``
come out false when the timed path is broken underneath: a step that
returns its state unchanged, half of the batch left out with the mean over
the rest, a token altered where the feed produces it, and, in the
four-chip cell, the exchange between chips left out (the update then sees
the first chip's quarter of the rows alone)."""
import dataclasses
import time

import jax
import jax.numpy as jnp
import pytest

from bench import harness


def _unchanged(session):
    from repro.train.steps import loss_fn

    metrics = jax.jit(lambda p, b: loss_fn(session.model, p, b)[1])

    def step(params, opt_state, batch):
        return params, opt_state, metrics(params, batch)

    session.override("compile", dataclasses.replace(session.compile(),
                                                    step_fn=step))


def _rows_only(fraction):
    def plant(session):
        compiled = session.compile()
        inner = compiled.step_fn

        def step(params, opt_state, batch):
            mask = batch["loss_mask"]
            keep = jnp.arange(mask.shape[0]) < int(mask.shape[0] * fraction)
            return inner(params, opt_state,
                         dict(batch, loss_mask=mask * keep[:, None]))

        session.override("compile", dataclasses.replace(compiled,
                                                        step_fn=step))
    return plant


def _altered_token(session):
    ds = session.dataset
    inner = ds.next_device_batch

    def feed():
        batch = inner()
        return dict(batch, tokens=batch["tokens"].at[0, 5].add(1))

    ds.next_device_batch = feed


def _run(cell, plant):
    return harness.run_cell(cell, 2**31 + 99, 0.3, False,
                            t_start=time.perf_counter(),
                            devices=jax.devices()[:cell.chips], plant=plant,
                            log=lambda s: None)


ONE_CHIP = [
    ("state_unchanged", _unchanged, "change_gap"),
    ("half_batch", _rows_only(0.5), "grad_gap"),
    ("token_altered", _altered_token, "feed_rows_wrong"),
]
FAULTS = ([("ds7b-train-uniform",) + f for f in ONE_CHIP]
          + [("ds7b-train-dp4",) + f for f in ONE_CHIP]
          + [("ds7b-train-dp4", "no_exchange", _rows_only(0.25), "grad_gap")])


@pytest.mark.parametrize("workload", ["ds7b-train-uniform", "ds7b-train-dp4"])
def test_sound_run_is_correct(workload, tiny_cell):
    assert _run(tiny_cell(workload), None)["correct"] is True


@pytest.mark.parametrize("workload,name,plant,check", FAULTS)
def test_broken_timed_path_is_not_correct(workload, name, plant, check,
                                          tiny_cell):
    out = _run(tiny_cell(workload), plant)
    assert out["correct"] is False
    c = out["checks"][check]
    assert c["value"] > c["limit"]
