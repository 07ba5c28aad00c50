"""The required-FLOP count and the peak table."""
import pytest

from bench import flops
from bench.harness import ROOT, _load_json

# hand count for deepseek-7b-2l at seq 2048 (PERF.md, section 3):
# per layer 4096*128*(32+64) + 32*128*4096 + 3*4096*11008 = 202,375,168
# matmul params 2 * 202,375,168 + 25,600*4096 (head) = 509,607,936
# 6 * 509,607,936 + 6 * 2 * 2048 * 32 * 128 = 3,158,310,912
DS7B_2L_FLOPS = 3_158_310_912
# deepseek-coder-33b-1l: one layer 66,060,288 + 51,380,224 + 412,876,800
# + 8,064*7168 head = 588,120,064; x6 + 6*1*2048*56*128
DSCODER_1L_FLOPS = 3_616_800_768


def test_deepseek_7b_2l_matches_the_hand_count():
    cfg = _load_json(ROOT / "bench/configs/deepseek-7b-2l.json")
    assert flops.matmul_params(cfg) == 509_607_936
    assert flops.train_flops_per_token(cfg, 2048) == DS7B_2L_FLOPS


def test_deepseek_coder_33b_1l_matches_the_hand_count():
    cfg = _load_json(ROOT / "bench/configs/deepseek-coder-33b-1l.json")
    assert flops.matmul_params(cfg) == 588_120_064
    assert flops.train_flops_per_token(cfg, 2048) == DSCODER_1L_FLOPS


def test_peak_table_knows_v5e_and_refuses_other_kinds():
    assert flops.peak("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        flops.peak("TPU v9 imaginary")
