"""Milliseconds a round of device self time under the program's `split`
scope (split evaluation, leaf values, node bookkeeping), in the traced
window (device trace)."""
from bench import scopes


def read(ctx):
    return scopes.per_unit(ctx, ("split",), "rounds", 1e3)
