"""Device time of the KDA layers' step kernel in one serving call, in ms:
the ops named ``tadnn_kda_step`` (every linear layer) WITH the compiler's
own copies of the state pools round them (``counts_gdn.staged_seconds``),
over the runs of ``jit_serve_prefill_chunk`` (which carries the decode rows)
and of ``jit_serve_decode_step`` where the traced seconds hold one, divided
by those runs (``lib/counts_kda.py``).  A program without the kernel gives
``None``."""
from lib import counts_kda


def read(rec):
    took, runs = counts_kda.step_seconds(rec)
    return 1e3 * took / runs if runs and took else None
