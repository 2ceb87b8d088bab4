"""Where a Pallas kernel runs: compiled for the chip, or interpreted.

Every kernel entry point under ``ops/`` takes ``interpret: bool | None``.
``None`` resolves HERE and nowhere else: compiled (Mosaic) when the
process's default backend is a TPU, the Pallas interpreter otherwise —
the interpreter is a correctness tool for the CPU tests, never a
deployment. An interpreted resolution raises a ``RuntimeWarning`` (shown
once per process by Python's default filter), so a run that finds no
chip cannot interpret its kernels silently; a run that must prove its
kernels compiled checks the program text for the Mosaic custom call
(:func:`has_compiled_kernel`, as ``chip_smoke.py`` does).
"""

from __future__ import annotations

import warnings

import jax


def default_interpret() -> bool:
    """True when kernels called with ``interpret=None`` will run in the
    Pallas interpreter (no TPU backend in this process)."""
    return jax.default_backend() != "tpu"


def resolve_interpret(interpret: bool | None) -> bool:
    """Resolve a kernel entry point's ``interpret`` argument. An explicit
    bool wins (tests force either mode); ``None`` follows
    :func:`default_interpret`."""
    if interpret is not None:
        return interpret
    chosen = default_interpret()
    if chosen:
        warnings.warn(
            f"no TPU backend (default backend {jax.default_backend()!r}): "
            "Pallas kernels run in the interpreter",
            RuntimeWarning,
            stacklevel=2,
        )
    return chosen


def has_compiled_kernel(program_text: str) -> bool:
    """True when a lowered/compiled program's text (``.as_text()``)
    carries a Mosaic kernel — the ``tpu_custom_call`` target an
    interpreted kernel never produces."""
    return "tpu_custom_call" in program_text
