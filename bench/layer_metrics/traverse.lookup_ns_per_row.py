"""Nanoseconds a scored row of device self time under the program's
`traverse/lookup` scope (the gather of each row's split-feature value),
in the traced window (device trace)."""
from bench import scopes


def read(ctx):
    return scopes.per_unit(ctx, ("traverse", "lookup"), "rows", 1e9)
