"""Host lattice construction per sweep: the program's ``dse.lattice_build``
(per-shape candidate lattices; absent when they are cached) and
``dse.network_grid_build`` (fusing them into lane buckets) spans,
summed, in ms."""

from chipbench.readers import span_ms_per_step


def read(ctx):
    return span_ms_per_step(ctx, ("dse.lattice_build",
                                  "dse.network_grid_build"))
