"""Share of the scheduler's lanes that carried a keystroke, over the
flushes of the traced slice: ``BatchStats.sum_occupancy`` over
``n_flushes`` times ``block``."""


def read(ctx):
    if not ctx.get("flushes"):
        return None
    return 100.0 * ctx["occupancy"] / (ctx["flushes"] * ctx["block"])
