"""Trace reduction: busy union, idle share, top ops, gaps named by the
harness span open on the host."""
import os

import pytest

from bench import xplane

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def _trace():
    ms = 1_000_000
    return {
        "devices": [[("fusion.1", 10 * ms, 30 * ms),
                     ("fusion.2", 25 * ms, 40 * ms),   # overlaps fusion.1
                     ("copy.3", 70 * ms, 80 * ms),
                     ("fusion.1", 95 * ms, 120 * ms)]],  # runs past the end
        "spans": [("bench.window", 0, 100 * ms),
                  ("bench.update", 0, 45 * ms),
                  ("bench.idle", 45 * ms, 68 * ms),
                  ("bench.update", 68 * ms, 100 * ms),
                  ("bench.other_thread", 0, 100 * ms)],
    }


def test_busy_union_and_idle_share():
    red = xplane.reduce(_trace())
    # busy: [10, 40] + [70, 80] + [95, 100] = 45 ms of a 100 ms window
    assert red["window_s"] == pytest.approx(0.1)
    assert red["busy_s"] == pytest.approx(0.045)
    assert red["idle_share"] == pytest.approx(0.55)


def test_top_ops_and_gaps_named_by_host_span():
    red = xplane.reduce(_trace())
    assert [n for n, _ in red["device_ops"]] == ["fusion.1", "fusion.2",
                                                 "copy.3"]
    assert red["device_ops"][0][1] == pytest.approx(0.025)  # clipped at 100
    # gaps: [0,10] update, [40,70] idle (midpoint 55), [80,95] update
    gaps = red["idle_gaps"]
    assert gaps[0][0] == "idle" and gaps[0][1] == pytest.approx(0.030)
    assert sorted(g[1] for g in gaps) == pytest.approx([0.01, 0.015, 0.03])
    assert {g[0] for g in gaps} == {"idle", "update"}


def test_no_window_or_no_device_gives_nothing():
    t = _trace()
    assert xplane.reduce({"devices": [], "spans": t["spans"]}) is None
    assert xplane.reduce({"devices": t["devices"], "spans": []}) is None


def test_recorded_chip_trace():
    """A trace recorded on one v5e chip: a matmul and a sort, three times
    each, between 2 ms host sleeps, inside a `bench.window` span."""
    trace = xplane.load(os.path.join(DATA, "tpu_small.xplane.pb"))
    assert len(trace["devices"]) == 1
    red = xplane.reduce(trace)
    assert red is not None
    assert 0.0 < red["busy_s"] < red["window_s"]
    assert 0.0 < red["idle_share"] < 1.0
    assert len(red["device_ops"]) >= 2
    assert "idle" in {name for name, _ in red["idle_gaps"]}
