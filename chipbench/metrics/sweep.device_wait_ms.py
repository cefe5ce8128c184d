"""Host waiting for device pricing per sweep: the program's
``dse.device_wait`` spans (the realization of each bucket's winners on
the host: the wait for the chip plus the device-to-host copy), summed,
in ms."""

from chipbench.readers import span_ms_per_step


def read(ctx):
    return span_ms_per_step(ctx, ("dse.device_wait",))
