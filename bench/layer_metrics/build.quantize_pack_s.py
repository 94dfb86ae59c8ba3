"""Seconds of `DeviceDMatrix(x, label, cuts=cuts)`: quantise and pack, until
the packed matrix is ready (host clock)."""


def read(ctx):
    return ctx["spans"].first("build.quantize_pack")
