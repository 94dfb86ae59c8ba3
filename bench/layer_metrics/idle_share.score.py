"""Share of the traced window in which no operation ran on the device
(device trace), in a scoring cell."""


def read(ctx):
    if ctx["trace"] is None:
        return None
    return 100.0 * ctx["trace"]["idle_share"]
