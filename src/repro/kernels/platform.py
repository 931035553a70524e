"""Where the Pallas kernels run: compiled by Mosaic on a TPU, through
the Pallas interpreter on the CPU.  Decided once, from the platform."""
from __future__ import annotations

from typing import Optional

import jax


def default_interpret(platform: Optional[str] = None) -> bool:
    """``interpret=`` for a kernel launched on ``platform`` (default:
    JAX's default backend): True on ``cpu``, False on ``tpu``.  Any
    other platform raises — there is no silent interpreter on a chip."""
    platform = platform or jax.default_backend()
    if platform == "cpu":
        return True
    if platform == "tpu":
        return False
    raise RuntimeError(
        f"Pallas kernels run compiled on 'tpu' or interpreted on 'cpu'; "
        f"platform {platform!r} is neither")


def resolve_interpret(interpret: Optional[bool]) -> bool:
    """A kernel's ``interpret`` argument as ``pallas_call`` takes it: an
    explicit bool stands, None (every default) is
    :func:`default_interpret`."""
    return default_interpret() if interpret is None else interpret
