"""Pallas kernel packages (maxpool, ocs_quant, flash_attention,
ocs_contention).  Each package is <name>.py (the kernel) + ops.py (jit'd
differentiable wrapper) + ref.py (pure-jnp oracle the parity suite compares
against, see tests/kernel_parity.py).

Interpret-mode policy: the kernels are written for the TPU.  On a TPU
backend they always compile (:func:`interpret_default` returns ``False``,
and asking for the interpreter there is an error, so a chip run can never
silently measure the interpreter).  On any other backend -- the CPU test
containers -- they run through the Pallas interpreter.
"""

from __future__ import annotations

import os


def fit_block(dim: int, want: int) -> int:
    """Largest divisor of ``dim`` that is <= ``want`` (VMEM tile auto-fit).

    Shared by every kernel package's tiling setup so odd shapes degrade to
    smaller-but-exact tiles instead of requiring padding.
    """
    b = min(want, dim)
    while dim % b != 0:
        b -= 1
    return b


_ENV_VAR = "REPRO_PALLAS_INTERPRET"
_TRUE = ("1", "true", "yes", "on")
_FALSE = ("0", "false", "no", "off")


def interpret_default() -> bool:
    """Should Pallas kernels run in interpreter mode by default?

    On a TPU backend: never.  The kernels compile, and
    ``REPRO_PALLAS_INTERPRET`` set to a true value raises instead of
    switching to the interpreter.

    Elsewhere: ``REPRO_PALLAS_INTERPRET`` (``1/true/yes/on`` or
    ``0/false/no/off``) overrides, read on every resolution (eager calls and
    each fresh jit trace; a value already baked into a cached jit executable
    persists until retrace); unset, the kernels interpret.
    """
    import jax

    on_tpu = jax.default_backend() == "tpu"
    env = os.environ.get(_ENV_VAR)
    if env is None:
        return not on_tpu
    val = env.strip().lower()
    if val not in _TRUE + _FALSE:
        raise ValueError(
            f"{_ENV_VAR}={env!r}: expected one of {_TRUE + _FALSE}")
    if on_tpu and val in _TRUE:
        raise RuntimeError(
            f"{_ENV_VAR}={env!r} on a TPU backend: the kernels compile on "
            "the chip and never fall back to the interpreter there")
    return val in _TRUE
