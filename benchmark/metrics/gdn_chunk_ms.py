"""Device time of the linear layers' chunk kernel in one prefill chunk, in
ms: the ops of the program ``jit_serve_prefill_chunk`` named
``tadnn_gdn_chunk`` (every linear layer), summed over the traced part and
divided by the runs of the program.  The kernel alone: the layer's XLA ops
(projections, the convolution, the decays folded into the operands, the
output norm and gate) carry no name of their own in a trace."""
from lib import counts_moe, serve_phases


def read(rec):
    took, runs = counts_moe.module_ops(
        rec, serve_phases.PREFILL_MODULE,
        lambda name: "tadnn_gdn_chunk" in name)
    return 1e3 * took / runs if runs and took else None
