"""The harness finds cells, configurations, traffic mixes and per-layer
metrics by the names BENCHMARK.json gives them: a cell added as two data
files (a configuration and a traffic mix) and two entries runs with no
edit to any code, and a per-layer metric is one new reader file."""
import json
import shutil
import time

import jax

from bench import harness

ROOT = harness.ROOT


def _checkout(tmp_path):
    root = tmp_path / "checkout"
    root.mkdir()
    shutil.copy(ROOT / "BENCHMARK.json", root / "BENCHMARK.json")
    shutil.copytree(ROOT / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    return root


def test_every_listed_cell_resolves_to_its_files():
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    for w in spec["workloads"]:
        cell = harness.find_cell(w["name"], ROOT)
        assert cell.config["name"] == w["config"]
        assert cell.traffic["kind"] == "train"
        assert [m["name"] for m in cell.end_to_end] == [
            "train_tokens_per_s", "setup_s"]
        for m in cell.per_layer:
            assert callable(harness.metric_reader(m["name"], ROOT))


def test_a_cell_added_as_data_files_runs_without_code_edits(tmp_path):
    root = _checkout(tmp_path)
    cfg = json.loads((root / "bench/configs/deepseek-7b-2l.json").read_text())
    cfg.update(name="tiny-dense", hidden_size=64, intermediate_size=128,
               num_attention_heads=4, num_key_value_heads=2, vocab_size=256,
               torch_dtype="float32", program={})
    (root / "bench/configs/tiny-dense.json").write_text(json.dumps(cfg))
    mix = json.loads((root / "bench/traffic/uniform-2x2.json").read_text())
    mix["seq_len"] = 32
    (root / "bench/traffic/tiny-mix.json").write_text(json.dumps(mix))
    (root / "bench/metrics/steps_in_window.py").write_text(
        "def read(ctx):\n    return float(ctx['steps'])\n")
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "tiny-dense", "source": "test",
                            "file": "bench/configs/tiny-dense.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": "tiny-train", "config": "tiny-dense",
                              "traffic": "tiny-mix", "chips": 1,
                              "why": "test"})
    spec["per_layer"].append({"name": "steps_in_window", "unit": "steps",
                              "better": "higher", "source": "host_clock",
                              "layer": "train step",
                              "moves": "train_tokens_per_s",
                              "workloads": ["tiny-train"]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))

    cell = harness.find_cell("tiny-train", root)
    assert cell.config["name"] == "tiny-dense"
    assert "steps_in_window" in [m["name"] for m in cell.per_layer]
    out = harness.run_cell(cell, 5, 0.5, False, t_start=time.perf_counter(),
                           devices=jax.devices()[:1], log=lambda s: None)
    assert out["correct"] is True
    assert out["metrics"]["train_tokens_per_s"]["value"] > 0
    assert list(out)[-1] == "checks"
    assert harness.metric_reader("steps_in_window", root)(
        {"steps": 3}) == 3.0
