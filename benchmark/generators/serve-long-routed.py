"""Kind ``serve-long-routed``: kind ``serve-long`` for a configuration whose
router scores its outputs by a SOFTMAX over hundreds of them.  The same
engine, arrivals, window, sample, verdict and reference calls; the router's
selection bias alone is drawn otherwise (``c n / width`` in the place of
``0.02 n``, under which one fixed dozen of outputs would take nearly every
token's choices).  See ``lib/serving_long_routed.py``."""

from lib import serving_long_routed


def run(ctx):
    return serving_long_routed.run(ctx)


def control(ctx):
    return serving_long_routed.run(ctx, control=True)
