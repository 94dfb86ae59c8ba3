"""Milliseconds a round of device self time under the program's
`histogram` scope (the root and level builds, the subtraction trick
included), in the traced window (device trace)."""
from bench import scopes


def read(ctx):
    return scopes.per_unit(ctx, ("histogram",), "rounds", 1e3)
