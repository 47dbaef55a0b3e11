"""Kind ``serve-large``: kind ``serve`` for a configuration too large to be
built the way ``lib/serving.py`` builds one (every weight in float32, the
reference ``reference/decoder.py``).  The same engine, arrivals, warm-up,
window, sample and verdict; the weights are held as the engine would hold
them and the reference is the one the configuration names.  See
``lib/serving_large.py``."""

from lib import serving_large


def run(ctx):
    return serving_large.run(ctx)


def control(ctx):
    return serving_large.run(ctx, control=True)
