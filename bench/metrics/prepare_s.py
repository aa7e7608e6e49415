"""Host seconds of ``NetworkPlan.prepare`` up to ``block_until_ready`` of
the prepared network."""


def read(ctx):
    return ctx.get("prepare_s")
