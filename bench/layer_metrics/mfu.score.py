"""Whole `predict` call's share of the chip's roofline: the least time one
call over the holdout needs (bench/roofline.py, from shapes) over the
measured time per call in the window (host clock)."""
from bench import reference, roofline


def read(ctx):
    if not ctx["result"].get("attempted"):
        return None
    cfg, res, sm = ctx["cfg"], ctx["result"], ctx["cfg"]["serve_model"]
    rows_per_call = res["rows"] / res["attempted"]
    bound = roofline.predict_call(
        rows_per_call, cfg["features"],
        reference.n_outputs(cfg["objective"], cfg["num_class"]),
        sm["trees"], sm["depth"], ctx["peaks"])
    ctx["notes"]["mfu.score"] = bound
    return 100.0 * bound["seconds"] * res["attempted"] / res["wall_s"]
