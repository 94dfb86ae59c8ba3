"""Milliseconds a call of device idle time in the traced window under a
`repro.*` host span other than `repro.round.wait`: the host work of
`Booster.update` that holds the device back (device trace, named by the
program's spans on the same clock)."""
from bench import scopes


def read(ctx):
    red, calls = scopes.from_ctx(ctx), ctx["result"].get("attempted")
    got = scopes.program_gaps(red, "repro.round.wait") if red else None
    return None if got is None or not calls else 1e3 * got / calls
