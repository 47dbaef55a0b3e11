"""Kind ``serve``: one ``ServeEngine`` under the mix's arrivals (an open
loop at a fixed rate, or a standing backlog).  See ``lib/serving.py``."""

from lib import serving


def run(ctx):
    return serving.run(ctx)


def control(ctx):
    return serving.run(ctx, control=True)
