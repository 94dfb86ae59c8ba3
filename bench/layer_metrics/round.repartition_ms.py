"""Milliseconds a round of device self time under the program's
`repartition` scope (rows routed to their children after each level's
splits), in the traced window (device trace)."""
from bench import scopes


def read(ctx):
    return scopes.per_unit(ctx, ("repartition",), "rounds", 1e3)
