"""How the program's executables and kernels are named in the device
trace: the one place the reduction matches on names.

On a TPU v5e the ``XLA Modules`` line names each run of an executable
``jit_<fn>(<fingerprint>)``.  The engine jits its admission prefill as
``pf`` and its paged decode step as ``step``; its recurrent decode step
is a ``functools.partial``, which JAX names ``_unknown``.  The ``XLA
Ops`` line names each operation by its HLO instruction,
``%<name>.<n> = <shape> <opcode>(...)``; a Pallas kernel is a
``custom-call`` named after the kernel's function
(``paged_decode_attention``, ``ssm_scan_scheduled``)."""
from __future__ import annotations

import re

_MODULE = re.compile(r"^jit_([A-Za-z0-9_]+)\(")
_OP = re.compile(r"^%([^\s=]+?)(\.\d+)* = ")


def module_fn(name: str) -> str:
    """The jitted function's name of an ``XLA Modules`` event."""
    m = _MODULE.match(name)
    return m.group(1) if m else name


def op_name(name: str) -> str:
    """The HLO instruction's name of an ``XLA Ops`` event, without its
    numeric suffix (``%fusion.146 = ...`` -> ``fusion``)."""
    m = _OP.match(name)
    return m.group(1) if m else name


def is_prefill_module(name: str) -> bool:
    return module_fn(name) == "pf"


def is_decode_module(name: str) -> bool:
    return module_fn(name) in ("step", "_unknown")


def is_paged_decode_kernel(name: str) -> bool:
    return op_name(name) == "paged_decode_attention"


def is_ssm_scan_kernel(name: str) -> bool:
    return op_name(name).startswith("ssm_scan")


# Operations that only hold others (their children appear as events of
# their own), left out of the per-operation breakdown.
CONTAINERS = ("while", "conditional", "call")
