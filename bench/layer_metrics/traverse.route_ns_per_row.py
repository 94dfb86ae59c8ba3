"""Nanoseconds a scored row of device self time under the program's
`traverse/route` scope (the routing-table gather and the child choice),
in the traced window (device trace)."""
from bench import scopes


def read(ctx):
    return scopes.per_unit(ctx, ("traverse", "route"), "rows", 1e9)
