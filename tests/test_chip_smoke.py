"""chip_smoke.py's control flow, rehearsed on the CPU at smoke widths.

The script itself refuses to run off the chip; these tests drive its train
and serve phase functions with ``smoke_config("deepseek-7b")`` so a broken
path shows up in tier-1 rather than in a chip call.
"""
import importlib.util
import sys
from pathlib import Path

import pytest

from repro.configs import smoke_config

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def chip_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", ROOT / "chip_smoke.py"
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_chip_config_keeps_published_widths(chip_smoke):
    from repro.configs import get_config

    full, cut = get_config("deepseek-7b"), chip_smoke.chip_config()
    for field in ("d_model", "n_heads", "n_kv_heads", "head_dim", "d_ff",
                  "dtype"):
        assert getattr(cut, field) == getattr(full, field), field
    assert (cut.n_layers, cut.vocab) == (2, 25_600)


def test_train_then_serve_phases_pass_at_smoke_size(chip_smoke):
    cfg = smoke_config("deepseek-7b")
    lines = []
    trained = chip_smoke.train_phase(cfg, steps=3, seq=32, log=lines.append)
    assert trained["compile_count"] == 1
    assert len(trained["losses"]) == 3
    assert trained["compile_seconds"], "no backend compile was observed"
    served = chip_smoke.serve_phase(
        cfg, trained["params"], n_requests=2, prompt_len=16, new_tokens=4,
        log=lines.append,
    )
    assert len(served["tokens"]) == 2
    # f32 smoke weights: the engine's greedy tokens match the forward exactly
    assert served["agree"] == 2 * 4
    assert any(line.startswith("train[synthetic]: losses") for line in lines)


def test_train_phase_fails_on_a_skipped_sharding_constraint(
    chip_smoke, monkeypatch
):
    import warnings

    def always_skip(x, *axes):
        # what with_logical_constraint emits when it drops a constraint
        warnings.warn(f"sharding constraint for logical axes {axes} skipped",
                      RuntimeWarning)
        return x

    monkeypatch.setattr("repro.models.layers.wlc", always_skip)
    with pytest.raises(RuntimeWarning, match="sharding constraint"):
        chip_smoke.train_phase(smoke_config("deepseek-7b"), steps=1, seq=16,
                               log=lambda _: None)


def test_main_refuses_a_host_without_a_tpu(chip_smoke, capsys):
    assert chip_smoke.main([]) != 0
    out = capsys.readouterr().out
    assert '"ok"' not in out
