"""Device time of the latent layers' chunk attention kernel in one prefill
chunk, in ms: the ops of the program ``jit_serve_prefill_chunk`` named
``tadnn_latent_chunk`` (every latent layer: a chunk's queries over the
slot's latent pages, each key block expanded on the chip), summed over the
traced part and divided by the runs of the program.  The kernel alone: the
queries' and the weights' way into its layout and the projections round it
are XLA ops.  ``None`` on a tree without the kernel."""
from lib import counts_moe, serve_phases


def read(rec):
    took, runs = counts_moe.module_ops(
        rec, serve_phases.PREFILL_MODULE,
        lambda name: "tadnn_latent_chunk" in name)
    return 1e3 * took / runs if runs and took else None
