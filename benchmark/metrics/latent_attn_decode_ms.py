"""Device time of the latent decode kernel in one call that decodes, in ms,
every latent layer of it: the ops named ``tadnn_paged_decode_latent``
summed over the traced part, over the calls that ran them (a call runs the
kernel once a latent layer, in the decode step and in a chunk that carries
the step's decode rows alike: a backlog of long prompts has hardly a call
without a chunk in its first seconds, which are the traced ones).  The
kernel alone: the absorption of the queries and the way back are XLA
products beside it."""
from lib import counts_mla


def read(rec):
    n = counts_mla.latent_layers(rec["model_keys"])[0]
    took_s, calls = counts_mla.kernel_seconds(rec)
    return 1e3 * took_s / (calls / n) if calls and n else None
