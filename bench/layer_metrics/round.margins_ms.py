"""Milliseconds a round of device self time under the program's `margins`
scope (each new tree's traversal of the training rows and the margins'
update), in the traced window (device trace)."""
from bench import scopes


def read(ctx):
    return scopes.per_unit(ctx, ("margins",), "rounds", 1e3)
