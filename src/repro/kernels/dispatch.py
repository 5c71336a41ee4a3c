"""Backend dispatch for the Pallas kernels: compiled vs interpret.

Every kernel entry point takes ``interpret=None`` ("auto") and routes it
through :func:`resolve_interpret`:

* ``PALLAS_INTERPRET=1`` in the environment forces interpret mode
  everywhere (the escape hatch for debugging a compiled backend);
  ``PALLAS_INTERPRET=0`` forces the compiled path.
* ``None`` / ``"auto"`` picks the compiled path exactly when the active
  JAX backend is a TPU (the kernels are written for Mosaic: TPU memory
  spaces and tilings) and interpret mode otherwise — on the CPU, where
  the tests run, auto means interpret.
* An explicit ``True`` / ``False`` is honoured as-is (absent the env
  override).

The resolver is a leaf module (imports only jax) so the individual
kernel files can use it without importing ``ops`` back.
"""

from __future__ import annotations

import os

import jax

__all__ = ["resolve_interpret"]


def resolve_interpret(interpret: bool | str | None = None) -> bool:
    """Resolve an ``interpret`` knob to a concrete bool.

    Precedence: ``PALLAS_INTERPRET`` env var, then an explicit bool,
    then backend auto-detection for ``None`` / ``"auto"``.
    """
    env = os.environ.get("PALLAS_INTERPRET")
    if env is not None and env.strip() != "":
        return env.strip() not in ("0", "false", "False")
    if interpret is None or interpret == "auto":
        return jax.default_backend() != "tpu"
    return bool(interpret)
