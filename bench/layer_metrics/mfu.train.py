"""Whole round's share of the chip's roofline: the least time a boosting
round needs (bench/roofline.py, from shapes) over the measured time per
round in the window (host clock)."""
from bench import reference, roofline


def read(ctx):
    if not ctx["result"].get("rounds"):
        return None
    cfg, res = ctx["cfg"], ctx["result"]
    bound = roofline.train_round(
        res["rows"], cfg["features"],
        reference.n_outputs(cfg["objective"], cfg["num_class"]),
        cfg["max_bin"], ctx["peaks"])
    ctx["notes"]["mfu.train"] = bound
    return 100.0 * bound["seconds"] * res["rounds"] / res["wall_s"]
