"""Fixtures of the benchmark's own tests (run them by path:
``python -m pytest bench/tests``; the repository's test run collects only
``tests/``).

``tiny_cell`` shrinks a real cell's configuration to a few thousand
parameters and 64-token rows, keeping its fleet, storage backend, optimizer
and schedule, so the harness's whole run fits a CPU test."""
import copy
import os
import sys
from pathlib import Path

# four virtual CPU devices, for the four-chip cell; set before JAX starts
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=4")

import pytest

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

TINY = dict(hidden_size=64, intermediate_size=128, num_attention_heads=4,
            num_key_value_heads=4, vocab_size=256)


@pytest.fixture
def tiny_cell():
    from bench import harness

    def make(name, dtype="float32", seq_len=64, **sizes):
        cell = harness.find_cell(name, ROOT)
        cfg = dict(cell.config, **TINY)
        cfg.update(sizes, torch_dtype=dtype, program={})
        cell.config = cfg
        cell.traffic = copy.deepcopy(cell.traffic)
        cell.traffic["seq_len"] = seq_len
        return cell

    return make
