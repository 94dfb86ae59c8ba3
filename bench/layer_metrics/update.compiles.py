"""Programs compiled or loaded from the persistent cache a call, the mean
over the window's `Booster.update` calls: the last `attempted` of the
program's `update` call records (`repro.obs`, program counter)."""


def read(ctx):
    try:
        from repro import obs
    except ImportError:  # a program that keeps no call records
        return None
    n = ctx["result"].get("attempted")
    recs = obs.calls("update")[-n:] if n else []
    if not n or len(recs) < n:
        return None
    by_span = {}
    for r in recs:
        for span, k in r["compiles"].items():
            by_span[span] = by_span.get(span, 0) + k
    ctx["notes"]["update.compiles"] = {"calls": n, "by_span": by_span}
    return sum(obs.compiles(r) for r in recs) / n
