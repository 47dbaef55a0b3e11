"""Device time of the KDA layers' chunk kernel in one call that carries a
prefill chunk, in ms: the ops of the program ``jit_serve_prefill_chunk``
named ``tadnn_kda_chunk`` (every linear layer), summed over the traced part
and divided by the runs of the program.  The kernel alone: the layer's XLA
ops (projections, the convolution, ``kda_products``'s block-by-block decays,
the output norm and gate) carry no name of their own in a trace.  A program
without the kernel gives ``None``."""
from lib import counts_kda


def read(rec):
    took, runs = counts_kda.chunk_seconds(rec)
    return 1e3 * took / runs if runs and took else None
