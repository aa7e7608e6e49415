"""The strided conv layers' share of their roofline, in percent
(``bench.lib.strided_work``): the least time of every layer with a
stride above 1 over the device time of the ops inside those layers'
``named_scope``s.  Nothing in a configuration without such layers, or
when the trace attributes no op to them."""
from bench.lib.strided_work import roofline_share


def read(ctx):
    return roofline_share(ctx, [l for l in ctx["cfg"]["layers"]
                                if l.get("stride", 1) > 1])
