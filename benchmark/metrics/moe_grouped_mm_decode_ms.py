"""Device time of the expert layers' grouped-matmul kernels in one decode
step, in ms: the ops of the program ``jit_serve_decode_step`` named
``tadnn_moe_grouped_mm*`` (gate-up and down, every expert layer), summed
over the traced part and divided by the runs of the program.  The kernels
alone, as the name says: the layer's XLA ops (routing, the sort, the
gathers, the shared expert, the float32 combine) carry no name of their own
in a trace and are not in this number, so work moved out of the kernels
would read as a gain here and shows in ``decode_device_ms`` only."""
from lib import counts_moe, serve_phases


def read(rec):
    took, runs = counts_moe.module_ops(
        rec, serve_phases.DECODE_MODULE,
        lambda name: "tadnn_moe_grouped_mm" in name)
    return 1e3 * took / runs if runs and took else None
