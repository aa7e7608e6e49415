"""The conv layers' share of their roofline, in percent: the least time of
every layer run in the window (max of work over peak FLOP/s and bytes over
peak bandwidth, ``bench.lib.work``) over the device time of the ops inside
the layers' ``named_scope``s in the trace.  Nothing when the trace
attributes no op to a layer."""
from bench.lib.work import least_time_s


def read(ctx):
    tr = ctx.get("trace")
    layers = ctx["cfg"]["layers"]
    if not tr:
        return None
    device_s = sum(tr["scope_s"].get(l["name"], 0.0) for l in layers)
    if device_s <= 0:
        return None
    floor = sum(least_time_s(l, ctx["batch"], ctx["peaks"]) for l in layers)
    return 100.0 * floor * ctx["steps"] / device_s
