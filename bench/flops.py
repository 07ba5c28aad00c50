"""Required operations of a training step, and the chip's peaks.

Counted from the configuration file's shapes, so no change to the program
can change them.  Required means what the model's mathematics needs, not
what the program happens to compute: rematerialized forward passes and
padding rows do not count.
"""
from __future__ import annotations

import json
from pathlib import Path
from typing import Dict

ROOT = Path(__file__).resolve().parents[1]


def matmul_params(cfg: Dict) -> int:
    """Parameters that enter a matrix product once per token: attention and
    feed-forward projections of every layer, and the output head (untied).
    The embedding lookup is a gather, not a product."""
    d = int(cfg["hidden_size"])
    h = int(cfg["num_attention_heads"])
    kv = int(cfg["num_key_value_heads"])
    hd = int(cfg.get("head_dim") or d // h)
    ff = int(cfg["intermediate_size"])
    layers = int(cfg["num_hidden_layers"])
    attn = d * hd * (h + 2 * kv) + h * hd * d
    mlp = 3 * d * ff        # SwiGLU: gate, up, down
    return layers * (attn + mlp) + int(cfg["vocab_size"]) * d


def train_flops_per_token(cfg: Dict, seq_len: int) -> int:
    """6 x matmul parameters (forward 2, backward 4), plus causal attention:
    scores and the weighted sum each take 2 * hd * (S / 2) per head on
    average in the forward pass, so 6 * S * H * hd per layer for forward
    and backward together."""
    d = int(cfg["hidden_size"])
    h = int(cfg["num_attention_heads"])
    hd = int(cfg.get("head_dim") or d // h)
    layers = int(cfg["num_hidden_layers"])
    return 6 * matmul_params(cfg) + 6 * layers * int(seq_len) * h * hd


def peak(device_kind: str, root: Path = ROOT) -> Dict:
    """The chip's published peaks; a kind missing from the table is an
    error, never a default."""
    with open(root / "bench" / "peaks.json") as f:
        table = json.load(f)
    if device_kind not in table["devices"]:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"known: {sorted(table['devices'])}")
    return table["devices"][device_kind]
