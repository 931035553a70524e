"""decode_step_ms: device time of the engine's decode-step executable in
the window divided by the number of its runs there, from the device
trace (``XLA Modules`` events of the decode program)."""
from harness.kernels import is_decode_module


def read(ctx):
    evs = ctx["trace"].module_events(is_decode_module)
    if not evs:
        return None
    return 1e3 * sum(e - s for _, _, s, e in evs) / 1e9 / len(evs)
