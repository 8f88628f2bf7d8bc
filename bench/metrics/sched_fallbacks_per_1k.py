"""Keystrokes whose lane result was inexact and went through the
one-shot path (``BatchStats.fallbacks``), per 1,000 answered in the
traced slice."""


def read(ctx):
    if "fallbacks" not in ctx or not ctx.get("answers"):
        return None
    return 1000.0 * ctx["fallbacks"] / ctx["answers"]
