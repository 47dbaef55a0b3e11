"""Device time of the linear layers' step kernel in one decode step, in ms:
the ops of the program ``jit_serve_decode_step`` named ``tadnn_gdn_step``
(every linear layer), summed over the traced part and divided by the runs of
the program.  The kernel alone, as ``gdn_chunk_ms`` says of its own, and
without the compiler's copies of the state pools round it, which overlap
other work (``gdn_step_roofline`` counts their windows)."""
from lib import counts_moe, serve_phases


def read(rec):
    took, runs = counts_moe.module_ops(
        rec, serve_phases.DECODE_MODULE,
        lambda name: "tadnn_gdn_step" in name)
    return 1e3 * took / runs if runs and took else None
