"""Kind ``serve-long``: kind ``serve-large`` for a configuration with a
whole vocabulary served at a long ``max_len`` and with ``linear_attention``
layers.  The same engine, arrivals, window, sample and verdict; the
reference is asked for the served positions' logits alone, and the linear
layers' decay leaves are drawn by the family's initialisation.  See
``lib/serving_long.py``."""

from lib import serving_long


def run(ctx):
    return serving_long.run(ctx)


def control(ctx):
    return serving_long.run(ctx, control=True)
