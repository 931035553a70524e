"""decode_host_ms: device idle time caused by the host inside decode
steps, per step: the idle gaps of the device (at least 20 us, as the
breakdown counts them) that lie inside the union of the engine's
``serve.decode_step.{upload,launch,fetch,check}`` spans in the window,
over the ``serve.decode_step.launch`` spans (one per step) that start in
it.  A program whose decode step records no phases yields nothing."""
from harness.spans import DECODE_PHASES, idle_ms_per


def read(ctx):
    return idle_ms_per(ctx["trace"], DECODE_PHASES,
                       "serve.decode_step.launch")
