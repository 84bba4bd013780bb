"""Pallas kernels of the training exchange and the serving path.

Every kernel takes ``interpret=None`` and resolves it through
`resolve_interpret`, the one place that picks how a kernel runs: compiled
on a TPU, in the Pallas interpreter on the CPU (tests and CPU
rehearsals). Any other platform is an error, never a silent fallback to
the interpreter or to a jnp reference.
"""
from __future__ import annotations

import jax


def resolve_interpret(interpret: bool | None = None) -> bool:
    """True when a Pallas kernel must run in interpret mode.

    An explicit bool wins (kernel tests pin interpret mode); ``None``
    follows `jax.default_backend()`: ``cpu`` interprets, ``tpu``
    compiles, anything else raises."""
    if interpret is not None:
        return bool(interpret)
    platform = jax.default_backend()
    if platform == "cpu":
        return True
    if platform == "tpu":
        return False
    raise RuntimeError(
        f"no Pallas kernel dispatch for platform {platform!r}: the kernels "
        "compile on 'tpu' and run interpreted on 'cpu'")
