"""Model registry: one uniform facade over every architecture family.

``get_model(cfg)`` returns a :class:`Model` whose methods dispatch to the family
module.  All entry points are pure functions of (params, inputs) so they can be
jit/pjit'd by the callers in :mod:`repro.train` and :mod:`repro.launch`.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional, Tuple

import jax

from repro.models.config import ModelConfig

PyTree = Any

_FAMILIES: Dict[str, Any] = {}


def _family(name: str):
    if name not in _FAMILIES:
        import importlib

        mod = {
            "dense": "repro.models.dense",
            "moe": "repro.models.moe",
            "rglru": "repro.models.rglru",
            "rwkv6": "repro.models.rwkv6",
            "encdec": "repro.models.encdec",
            "vlm": "repro.models.vlm",
        }[name]
        _FAMILIES[name] = importlib.import_module(mod)
    return _FAMILIES[name]


@dataclasses.dataclass(frozen=True)
class Model:
    """Uniform interface. ``forward`` returns (logits, aux_loss)."""

    cfg: ModelConfig
    mod: Any

    # -- params ---------------------------------------------------------------
    def init_params(self, key=None, abstract: bool = False, dtype=None):
        return self.mod.init_params(self.cfg, key=key, abstract=abstract, dtype=dtype)

    # -- compute --------------------------------------------------------------
    def forward(self, params, tokens, **inputs) -> Tuple[jax.Array, jax.Array]:
        logits, aux, _ = self.forward_with_stats(params, tokens, **inputs)
        return logits, aux

    def forward_with_stats(self, params, tokens, **inputs) -> Tuple[
            jax.Array, jax.Array, Dict[str, jax.Array]]:
        """-> (logits, aux_loss, stats): ``stats`` holds the counters a
        family's step reports (the dropless MoE's ``moe_rows``), else {}."""
        out = self.mod.forward(params, self.cfg, tokens, **inputs)
        if not isinstance(out, tuple):
            import jax.numpy as jnp

            return out, jnp.zeros((), jnp.float32), {}
        return out if len(out) == 3 else (*out, {})

    def decode_step(self, params, token, cache, pos):
        return self.mod.decode_step(params, self.cfg, token, cache, pos)

    def prefill(self, params, tokens, cache_len: int, **inputs):
        return self.mod.prefill(params, self.cfg, tokens, cache_len, **inputs)

    # -- caches ---------------------------------------------------------------
    def init_cache(self, batch: int, cache_len: int, dtype=None):
        return self.mod.init_cache(self.cfg, batch, cache_len, dtype=dtype)

    def cache_logical_axes(self):
        return self.mod.cache_logical_axes(self.cfg)

    def abstract_cache(self, batch: int, cache_len: int, dtype=None):
        """ShapeDtypeStruct cache (dry-run, no allocation)."""
        fn = lambda: self.init_cache(batch, cache_len, dtype=dtype)
        return jax.eval_shape(fn)

    @property
    def has_decoder(self) -> bool:
        return True  # all assigned archs decode (whisper is enc-DEC)

    @property
    def supports_long_context(self) -> bool:
        """Sub-quadratic archs run the long_500k shape."""
        return self.cfg.family in ("rglru", "rwkv6")


def get_model(cfg: ModelConfig) -> Model:
    return Model(cfg=cfg, mod=_family(cfg.family))
