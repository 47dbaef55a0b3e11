"""Device time of the state-space layers' step kernel in one serving call,
in ms: the ops named ``tadnn_ssm_step`` (every state-space layer) WITH the
compiler's own copies of the state pools round them
(``counts_gdn.staged_seconds``), over the runs of
``jit_serve_prefill_chunk`` (which carries the decode rows) and of
``jit_serve_decode_step`` where the traced seconds hold one, divided by those
runs (``lib/counts_ssm.py``).  A program without the kernel gives
``None``."""
from lib import counts_ssm


def read(rec):
    took, runs = counts_ssm.step_seconds(rec)
    return 1e3 * took / runs if runs and took else None
