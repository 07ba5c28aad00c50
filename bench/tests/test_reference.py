"""The plain reference against the program's Session.run, on the CPU at a
small size in float32: the first steps' losses, the first gradient (read
from AdamW's first moment) and each leaf's change agree to float32
rounding, on the uniform fleet and on the masked (Algorithm 1) fleet."""
import jax
import pytest

from bench import harness

# float32 on both sides: only the order of summation differs
LOSS_RTOL = 1e-5
NORM_GAP = 1e-4


@pytest.mark.parametrize("workload,sizes", [
    ("ds7b-train-uniform", {}),
    ("ds7b-train-stannis", {}),
    # grouped-query attention at the 33b's 7:1 grouping
    ("dscoder33b-train-uniform", dict(hidden_size=112,
                                      num_attention_heads=7,
                                      num_key_value_heads=1)),
])
def test_reference_follows_session_run(workload, sizes, tiny_cell, tmp_path):
    cell = tiny_cell(workload, **sizes)
    seed = 2**33 + 7
    session, params, opt, feed, rd = harness.setup_program(
        cell, seed, jax.devices()[:1], str(tmp_path))
    del session, params, opt, feed
    ref = harness.reference_module("dense")
    out = ref.train_readings(cell.config, cell.traffic, seed, steps=3)

    assert rd.groups == cell.traffic["groups"]
    assert harness.feed_mismatches(cell, ref, seed, rd.batches) == 0
    g = harness.gaps(rd.losses, rd.grad_norms, rd.change_norms, out)
    assert all(g[f"loss_gap_{t}"] <= LOSS_RTOL for t in (1, 2, 3)), g
    assert g["grad_gap"] <= NORM_GAP and g["change_gap"] <= NORM_GAP, g


def test_masked_rows_do_not_count(tiny_cell):
    """The stannis layout pads 8 rows to hold 5: the reference's loss over
    the 5 valid rows is the program's, and differs from all 8 rows'."""
    cell = tiny_cell("ds7b-train-stannis")
    ref = harness.reference_module("dense")
    ml, valid = ref.padded_layout(cell.traffic)
    assert (ml, int(valid.sum()), valid.size) == (2, 5, 8)
    rows = ref.step_rows(cell.config, cell.traffic, 11, 0)
    assert rows.shape == (5, cell.traffic["seq_len"] + 1)
