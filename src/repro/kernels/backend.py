"""Where the Pallas kernels run: compiled on a TPU, emulated elsewhere.

The one place that maps the platform to a kernel backend.  Every kernel
wrapper takes ``interpret=None`` and resolves it here at trace time:

- on a TPU the compiled Mosaic kernel always runs — asking for the Pallas
  interpreter or the ``lax.scan`` emulation there is an error, so a chip
  run can never silently measure the CPU path;
- on any other backend (the CPU the tests run on) ``None`` means the
  interpreter, the item-sweep ops' ``impl='auto'`` picks their ``lax.scan``
  backend, and the cross-encoder scorers attend with the plain ``jnp``
  reference (the interpreted flash kernel costs ~100x its arithmetic on
  CPU).  Explicit ``interpret=True`` / ``impl=...`` / ``attn_impl=...`` stay
  available there for the kernel parity tests.
"""

from __future__ import annotations

from typing import Optional

import jax


def on_tpu() -> bool:
    """Whether JAX's default backend is a TPU."""
    return jax.default_backend() == "tpu"


def resolve_interpret(interpret: Optional[bool]) -> bool:
    """Pallas interpret mode for this platform (``None`` = choose)."""
    if on_tpu():
        if interpret:
            raise ValueError(
                "Pallas interpret mode was requested on a TPU; the compiled "
                "kernel runs there (pass interpret=None)"
            )
        return False
    return True if interpret is None else bool(interpret)


def resolve_impl(impl: str, interpret: Optional[bool]) -> tuple:
    """(impl, interpret) of an item-sweep op for this platform.

    ``impl='auto'`` is the compiled Pallas kernel on a TPU and the
    ``lax.scan`` emulation elsewhere (or the Pallas interpreter when
    ``interpret=False`` asks for the kernel itself).  ``'scan'`` on a TPU
    raises: the emulation is a CPU reference, never a chip path.
    """
    if impl not in ("auto", "scan", "pallas"):
        raise ValueError(f"unknown impl '{impl}' (auto|scan|pallas)")
    interpret = resolve_interpret(interpret)
    if impl == "scan" and on_tpu():
        raise ValueError(
            "the lax.scan item sweep is the CPU emulation; a TPU runs the "
            "compiled Pallas kernel (impl='auto')"
        )
    if impl == "auto":
        impl = "scan" if interpret else "pallas"
    return impl, interpret


def resolve_attn_impl(attn_impl: Optional[str]) -> str:
    """Attention of the cross-encoder scorers (``None`` = choose): the flash
    kernel on a TPU, the ``jnp`` reference elsewhere."""
    if attn_impl is None:
        return "flash" if on_tpu() else "ref"
    if attn_impl not in ("flash", "ref"):
        raise ValueError(f"unknown attn_impl '{attn_impl}' (flash|ref)")
    return attn_impl
