"""Pallas execution mode, decided where a kernel is called."""
from __future__ import annotations

import jax


def interpret_mode(interpret: bool | None) -> bool:
    """``interpret`` as given, else the mode the default backend needs: the
    Pallas interpreter on the CPU and compiled Mosaic on the TPU.  Any other
    platform raises, so a kernel never falls back to the interpreter on an
    accelerator without being asked to."""
    if interpret is not None:
        return interpret
    platform = jax.default_backend()
    if platform == "cpu":
        return True
    if platform == "tpu":
        return False
    raise RuntimeError(f"no Pallas kernel mode for platform {platform!r}; "
                       f"pass interpret= explicitly")
