"""Host result assembly per sweep: the program's ``dse.assemble`` spans
(per-network totals and results after pricing; in a serving sweep also
the per-point phase and KV-hierarchy fold), summed, in ms."""

from chipbench.readers import span_ms_per_step


def read(ctx):
    return span_ms_per_step(ctx, ("dse.assemble",))
