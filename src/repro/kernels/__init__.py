"""Pallas kernels for the hot spots the platform optimizes.

Every kernel package has ``<name>.py`` (the Pallas kernel), ``ref.py`` (the
pure-jnp reference) and ``ops.py`` (the public entry point).  Each entry
point takes ``impl``:

* ``"pallas"`` — the compiled Mosaic kernel.  It needs a TPU backend and
  raises anywhere else: a run that asks for the kernel never silently gets
  the interpreter;
* ``"pallas_interpret"`` — the same kernel under the Pallas interpreter, the
  only way to run it off a TPU (CPU tests);
* ``"ref"`` / ``"chunked"`` — jnp implementations;
* ``"auto"`` — ``"pallas"`` on a TPU, the jnp path elsewhere.
"""
from __future__ import annotations

import jax


def on_tpu() -> bool:
    """True when the default JAX backend is a TPU."""
    return jax.default_backend() == "tpu"


def pallas_interpret(impl: str) -> bool:
    """The ``interpret`` flag of a ``pallas_call`` for ``impl``.

    ``"pallas_interpret"`` interprets; ``"pallas"`` compiles and raises when
    the backend is not a TPU.
    """
    if impl == "pallas_interpret":
        return True
    if impl != "pallas":
        raise ValueError(f"not a Pallas impl: {impl!r}")
    if not on_tpu():
        raise RuntimeError(
            f"impl='pallas' runs the compiled TPU kernel, but the JAX backend "
            f"is {jax.default_backend()!r}; use impl='pallas_interpret' to "
            f"interpret the kernel off a TPU")
    return False
