"""Images whose forward finished in the window, over the window."""


def read(ctx):
    return ctx["images"] / ctx["window_s"]
