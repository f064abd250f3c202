"""Pallas kernels of the device plane, their jnp references (``ref``) and
the public dispatch wrappers (``ops``).

:func:`interpret_mode` is the one place that decides whether a Pallas call
runs in the interpreter: every wrapper here takes ``interpret=None`` and
resolves it through this function, so no caller interprets a kernel on a
TPU without asking for it.
"""

from __future__ import annotations


def interpret_mode(interpret: bool | None = None) -> bool:
    """``interpret`` when given, else True exactly when JAX's default
    backend is the CPU (which has no Pallas lowering): the tests run the
    kernels interpreted there, and a TPU compiles them through Mosaic."""
    if interpret is not None:
        return bool(interpret)
    import jax   # lazy: contracts.py is imported by the jax-free linter
    return jax.default_backend() == "cpu"
