"""(IMC-eligible layer instance, design) pairs whose best (mapping,
dataflow) was found, over all sweeps the window completed, divided by the
window's whole time (the last sweep's overrun included)."""

from chipbench.readers import units_per_s as read  # noqa: F401
