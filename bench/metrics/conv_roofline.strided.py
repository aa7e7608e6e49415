"""Every conv layer's share of its roofline, in percent, counted with its
stride (``bench.lib.strided_work``): the least time of every layer run in
the window over the device time of the ops inside the layers'
``named_scope``s.  Nothing when the trace attributes no op to a layer."""
from bench.lib.strided_work import roofline_share


def read(ctx):
    return roofline_share(ctx, ctx["cfg"]["layers"])
