"""Programs launched on the device per one-shot batch in the traced
slice (events on the trace's ``XLA Modules`` line): 1 with no exactness
retry, one more per retry round."""


def read(ctx):
    if not ctx.get("batches"):
        return None
    return ctx["launches"] / ctx["batches"]
