"""Device busy time per answer resolved in the traced slice."""


def read(ctx):
    if not ctx.get("answers"):
        return None
    return 1e6 * ctx["busy_s"] / ctx["answers"]
