"""Device busy time per scheduler flush in the traced slice."""


def read(ctx):
    if not ctx.get("flushes"):
        return None
    return 1e3 * ctx["busy_s"] / ctx["flushes"]
