"""Pallas TPU kernels for the framework's compute hot spots.

STANNIS itself contributes at the distribution layer; these kernels make the
per-chip layer fast: flash/decode attention (transformer hot spots), RG-LRU
and WKV6 scans (recurrent archs, chunked-parallel TPU forms), and int8
quantization (the compressed-allreduce building block).

Models call :mod:`repro.kernels.ops`; oracles live in :mod:`repro.kernels.ref`.
:func:`interpret_default` is the one place that decides whether a Pallas
kernel runs in the interpreter.
"""
import jax


def interpret_default() -> bool:
    """Run Pallas kernels in the interpreter: only on the CPU backend."""
    return jax.default_backend() == "cpu"


from repro.kernels import ops, ref  # noqa: E402  (ops imports the above)

__all__ = ["interpret_default", "ops", "ref"]
