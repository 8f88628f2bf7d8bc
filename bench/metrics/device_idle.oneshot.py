"""Share of the traced slice in which no operation ran on the device,
in the one-shot cell."""


def read(ctx):
    return ctx["idle_pct"]
