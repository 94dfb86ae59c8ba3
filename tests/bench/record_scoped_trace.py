"""Record the small scoped trace that `tests/bench/test_bench_scopes.py`
reads: on one chip, a tiny `Booster.fit`, two `Booster.update` calls and a
`Booster.predict`, each inside a harness span (`bench.fit`, `bench.update`,
`bench.predict`) within `bench.window`, as `bench/run.py` traces a cell.
The round programs and the traversal are compiled before the trace; the
second update still compiles its eager ensemble concatenations inside it.
To keep the file small the Python tracer is off, and the written file
leaves out the `/host:metadata` plane (the programs' HLO protos, which no
reduction reads); every other plane is copied byte for byte.

    PYTHONPATH=src python3 tests/bench/record_scoped_trace.py [out.xplane.pb]

Without a TPU it exits 3 and writes nothing.
"""
import os
import shutil
import sys
import tempfile

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

OUT = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                   "tpu_scoped.xplane.pb")
SEED, ROWS, FEATURES = 20261017, 4096, 4
PARAMS = dict(n_rounds=2, max_depth=3, max_bins=16)
DROPPED_PLANE = "/host:metadata"


def without_plane(buf: bytes, name: str) -> bytes:
    """The serialized XSpace `buf` without its plane called `name`."""
    from bench import scopes

    out, i = bytearray(), 0
    while i < len(buf):
        key, j = scopes.varint(buf, i)
        if key & 7 != 2:
            raise ValueError("an XSpace holds length-delimited fields only")
        n, j = scopes.varint(buf, j)
        end = j + n
        drop = key >> 3 == scopes.SPACE_PLANES and any(
            f == scopes.PLANE_NAME and scopes.text(buf, v) == name
            for f, v in scopes.fields(buf, j, end))
        if not drop:
            out += buf[i:end]
        i = end
    return bytes(out)


def main(out: str = OUT) -> int:
    import jax

    from bench import drivers, xplane
    from repro.core import Booster, BoosterConfig, DeviceDMatrix

    if jax.devices()[0].platform != "tpu":
        print("record_scoped_trace: needs a TPU", file=sys.stderr)
        return 3
    rng = np.random.default_rng(SEED)
    x = rng.normal(size=(ROWS, FEATURES)).astype(np.float32)
    y = (x[:, 0] + 0.5 * x[:, 1] > 0).astype(np.float32)
    dtrain = DeviceDMatrix(x, label=y, max_bins=PARAMS["max_bins"])
    # Compile before the trace: the fit's and the updates' round programs,
    # the first update's concatenations, and the traversal of the four
    # trees the traced predict scores. The second traced update compiles
    # its concatenations in the trace, as every update of a cell does.
    Booster(BoosterConfig(**PARAMS)).fit(dtrain).update(dtrain, 1)
    four = Booster(BoosterConfig(**dict(PARAMS, n_rounds=4))).fit(dtrain)
    np.asarray(four.predict(x[:1024]))

    spans = drivers.Spans()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    trace_dir = tempfile.mkdtemp(prefix="scoped-trace-")
    try:
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
        with spans("window"):
            with spans("fit"):
                booster = Booster(BoosterConfig(**PARAMS)).fit(dtrain)
                drivers.ready(booster.margins)
            for _ in range(2):
                with spans("update"):
                    booster.update(dtrain, 1)
                    drivers.ready(booster.margins)
            with spans("predict"):
                np.asarray(booster.predict(x[:1024]))
        jax.profiler.stop_trace()
        with open(xplane.find_xplane(trace_dir), "rb") as fh:
            buf = without_plane(fh.read(), DROPPED_PLANE)
        with open(out, "wb") as fh:
            fh.write(buf)
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)
    print(f"record_scoped_trace: {os.path.getsize(out)} bytes to {out}")
    return 0


if __name__ == "__main__":
    sys.exit(main(*sys.argv[1:]))
