"""Device time of the state-space layers' chunk kernel in one prefill
chunk, in ms: the ops named ``tadnn_ssm_chunk`` (every state-space layer)
inside ``jit_serve_prefill_chunk``, over the runs of that program in the
traced part (``lib/counts_ssm.py``).  A program
without the kernel gives ``None``."""
from lib import counts_ssm


def read(rec):
    took, runs = counts_ssm.chunk_seconds(rec)
    return 1e3 * took / runs if runs and took else None
