"""Set-up: seconds from process start to the first timed step (device
init, weights, plan, prepare, compile or cache load, warm-up)."""


def read(ctx):
    return ctx["setup_s"]
