"""The control: the reference put in the program's place with every matrix
product in int8 (one scale per tensor), one precision step below the
bfloat16 the configurations state.  At a small size on the CPU it fails at
least one of each cell's limits, and reads at least ten times what the
program itself (bf16, through Session.run) reads on that number."""
import jax
import pytest

from bench import harness

CELLS = ["ds7b-train-uniform", "dscoder33b-train-uniform",
         "ds7b-train-stannis", "ds7b-train-dp4"]
SEED = 2**32 + 321


@pytest.fixture(scope="module")
def readings():
    from conftest import TINY

    cell = harness.find_cell("ds7b-train-uniform")
    cell.config = dict(cell.config, **TINY, torch_dtype="bfloat16",
                       program={})
    cell.traffic = dict(cell.traffic, seq_len=64)
    ref = harness.reference_module("dense")
    base = ref.train_readings(cell.config, cell.traffic, SEED)
    control = ref.train_readings(cell.config, cell.traffic, SEED,
                                 precision="int8")
    _, _, _, _, rd = harness.setup_program(cell, SEED, jax.devices()[:1],
                                           None)
    return (harness.gaps(rd.losses, rd.grad_norms, rd.change_norms, base),
            harness.gaps(control["losses"], control["grad_norms"],
                         control["change_norms"], base))


@pytest.mark.parametrize("workload", CELLS)
def test_control_fails_the_limits(workload, readings):
    limits = harness.find_cell(workload).traffic["limits"]
    program, control = readings
    failed = [k for k, v in limits.items()
              if v is not None and control[k] > v]
    assert failed, control
    assert any(control[k] >= 10 * program[k] for k in failed), (program,
                                                                control)
