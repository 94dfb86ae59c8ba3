"""Seconds of the cut build: the host rows onto the device, then
`repro.core.compute_cuts` until its cuts are ready (host clock)."""


def read(ctx):
    return ctx["spans"].first("build.cuts")
