"""Main thread waiting for the host lattice build per sweep: the
program's ``dse.await_bucket`` spans (each wait on the builder's bucket
queue, the last one for its end included), summed, in ms."""

from chipbench.readers import span_ms_per_step


def read(ctx):
    return span_ms_per_step(ctx, ("dse.await_bucket",))
