"""Device idle share of a closed-loop window, in percent: 1 - (union of
device-op intervals) / (traced window)."""
from bench.lib.trace import idle_share


def read(ctx):
    return idle_share(ctx.get("trace"))
