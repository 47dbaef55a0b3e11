"""Device time of the expert layers' grouped-matmul kernels in one prefill
chunk, in ms: the ops of the program ``jit_serve_prefill_chunk`` named
``tadnn_moe_grouped_mm*``, summed over the traced part and divided by the
runs of the program.  A chunk of 512 tokens reads every held expert of every
expert layer; chunks are not fenced, so they have no counters and no
roofline share of their own: this is their kernels' time alone."""
from lib import counts_moe, serve_phases


def read(rec):
    took, runs = counts_moe.module_ops(
        rec, serve_phases.PREFILL_MODULE,
        lambda name: "tadnn_moe_grouped_mm" in name)
    return 1e3 * took / runs if runs and took else None
