"""External-memory training path (DESIGN.md §11).

The headline guarantee: training over an artificially chunked
ExternalDMatrix is BIT-IDENTICAL to single-shot training on the same data
— same trees, same margins, same predictions — because the chunked round
performs the same f32 operations in the same order (per-bin scatter order,
one barriered margin add). Plus: from_batches assembly identity, batch
validation errors, eval sets / early stopping / continuation over chunks,
and sketch-cut training quality.
"""
import numpy as np
import pytest

from repro.core import Booster, DeviceDMatrix, ExternalDMatrix
from repro.core import compress as C

ENSEMBLE_FIELDS = (
    "feature",
    "split_bin",
    "threshold",
    "default_left",
    "leaf_value",
    "is_leaf",
)


def assert_boosters_identical(b1, b2):
    for fld in ENSEMBLE_FIELDS:
        np.testing.assert_array_equal(
            np.asarray(getattr(b1.ensemble, fld)),
            np.asarray(getattr(b2.ensemble, fld)),
            err_msg=f"ensemble field {fld} differs",
        )


@pytest.fixture(scope="module")
def data():
    rng = np.random.default_rng(7)
    n, f = 3000, 8
    x = rng.standard_normal((n, f)).astype(np.float32)
    x[rng.random((n, f)) < 0.05] = np.nan
    w = rng.standard_normal(f).astype(np.float32)
    y = ((np.nan_to_num(x) @ w + 0.3 * rng.standard_normal(n)) > 0).astype(
        np.float32
    )
    return x, y


def test_multi_chunk_fit_bit_identical_to_single_shot(data):
    """The acceptance criterion: fit over >= 4 chunks (last one short)
    equals the in-memory fit bit for bit."""
    x, y = data
    dtrain = DeviceDMatrix(x, label=y)
    ext = ExternalDMatrix.from_arrays(x, y, chunk_rows=700, cuts="exact")
    assert ext.n_chunks == 5  # 4 full chunks + a short one
    b1 = Booster(n_rounds=10, max_depth=4, objective="binary:logistic").fit(dtrain)
    b2 = Booster(n_rounds=10, max_depth=4, objective="binary:logistic").fit(ext)
    assert_boosters_identical(b1, b2)
    np.testing.assert_array_equal(np.asarray(b1.margins), np.asarray(b2.margins))
    np.testing.assert_array_equal(
        np.asarray(b1.predict(x)), np.asarray(b2.predict(x))
    )
    # bin-space prediction over the chunked matrix agrees with flat
    np.testing.assert_array_equal(
        np.asarray(b2.predict(ext)), np.asarray(b1.predict(dtrain))
    )


def test_multiclass_chunked_bit_identical(data):
    x, _ = data
    rng = np.random.default_rng(11)
    y = rng.integers(0, 3, x.shape[0]).astype(np.float32)
    d = DeviceDMatrix(x, label=y)
    e = ExternalDMatrix.from_arrays(x, y, chunk_rows=640, cuts="exact")
    kw = dict(n_rounds=6, max_depth=3, objective="multi:softmax", n_classes=3)
    assert_boosters_identical(Booster(**kw).fit(d), Booster(**kw).fit(e))


def test_update_continuation_matches_longer_fit(data):
    x, y = data
    ext = ExternalDMatrix.from_arrays(x, y, chunk_rows=800, cuts="exact")
    long = Booster(n_rounds=8, max_depth=3, objective="binary:logistic").fit(ext)
    short = Booster(n_rounds=5, max_depth=3, objective="binary:logistic").fit(ext)
    short.update(ext, 3)
    assert_boosters_identical(long, short)


def test_external_eval_sets_and_early_stopping(data):
    x, y = data
    rng = np.random.default_rng(5)
    xv = rng.standard_normal((600, x.shape[1])).astype(np.float32)
    yv = (rng.random(600) < 0.5).astype(np.float32)
    ext = ExternalDMatrix.from_arrays(x, y, chunk_rows=800)
    dvalid = ExternalDMatrix.from_arrays(xv, yv, chunk_rows=250, ref=ext)
    bst = Booster(n_rounds=40, max_depth=3, objective="binary:logistic").fit(
        ext, evals=[(dvalid, "valid")], early_stopping_rounds=4
    )
    assert bst.best_iteration is not None
    assert bst.n_rounds_trained == bst.best_iteration + 1
    assert any(k.startswith("valid_") for k in bst.history[0])
    # mixed eval types work too: a DeviceDMatrix sharing the external cuts
    dv2 = DeviceDMatrix(xv, label=yv, ref=ext)
    res = bst.eval(dv2, name="v2", metrics="logloss")
    assert np.isfinite(res["v2_logloss"])


def test_from_batches_identity(data):
    """DeviceDMatrix.from_batches == DeviceDMatrix on the concatenation,
    bit for bit (packed words, cuts, labels and the resulting fit)."""
    x, y = data
    chunks = [
        (x[:1000], y[:1000]),
        (x[1000:1500], y[1000:1500]),
        (x[1500:], y[1500:]),
    ]
    d1 = DeviceDMatrix(x, label=y)
    d2 = DeviceDMatrix.from_batches(iter(chunks))
    np.testing.assert_array_equal(
        np.asarray(d1.matrix.packed), np.asarray(d2.matrix.packed)
    )
    np.testing.assert_array_equal(np.asarray(d1.cuts), np.asarray(d2.cuts))
    np.testing.assert_array_equal(np.asarray(d1.label), np.asarray(d2.label))
    b1 = Booster(n_rounds=5, max_depth=3, objective="binary:logistic").fit(d1)
    b2 = Booster(n_rounds=5, max_depth=3, objective="binary:logistic").fit(d2)
    assert_boosters_identical(b1, b2)


def test_batch_validation_errors(data):
    """The satellite fix: inconsistent batches fail fast with a clear error
    naming the offending batch, not an opaque XLA shape error."""
    x, y = data
    with pytest.raises(ValueError, match="batch 1 has 4 features"):
        DeviceDMatrix.from_batches([x[:10, :8], x[10:20, :4]])
    with pytest.raises(ValueError, match="batch 1 has dtype"):
        DeviceDMatrix.from_batches([x[:10], x[10:20].astype(np.float64)])
    with pytest.raises(ValueError, match="batch 0 must be 2-D"):
        DeviceDMatrix.from_batches([x[0]])
    with pytest.raises(ValueError, match="non-numeric"):
        DeviceDMatrix.from_batches([np.array([["a", "b"], ["c", "d"]])])
    with pytest.raises(ValueError, match="label has 3 rows"):
        DeviceDMatrix.from_batches([(x[:10], y[:3])])
    with pytest.raises(ValueError, match="label"):
        DeviceDMatrix.from_batches([(x[:10], y[:10]), x[10:20]])
    with pytest.raises(ValueError, match="no batches"):
        DeviceDMatrix.from_batches([])
    with pytest.raises(ValueError, match="batch 1 is empty"):
        ExternalDMatrix([x[:10], x[:0]], chunk_rows=8)
    with pytest.raises(ValueError, match="chunk_rows"):
        ExternalDMatrix.from_arrays(x, y, chunk_rows=0)
    with pytest.raises(ValueError, match="cuts must be"):
        ExternalDMatrix.from_arrays(x, y, chunk_rows=512, cuts="bogus")


def test_rechunking_arbitrary_batch_sizes(data):
    """Incoming batch sizes need not match chunk_rows: rows are re-sliced
    into uniform chunks and the fit stays bit-identical."""
    x, y = data
    sizes = [123, 1001, 7, 869, 1000]
    chunks, start = [], 0
    for s in sizes:
        chunks.append((x[start : start + s], y[start : start + s]))
        start += s
    e1 = ExternalDMatrix(iter(chunks), chunk_rows=512, cuts="exact")
    e2 = ExternalDMatrix.from_arrays(x, y, chunk_rows=512, cuts="exact")
    assert e1.n_chunks == e2.n_chunks == 6
    np.testing.assert_array_equal(e1._host_packed, e2._host_packed)
    np.testing.assert_array_equal(np.asarray(e1.label), np.asarray(e2.label))


def test_sketch_cuts_training_quality(data):
    """Default (sketch) cuts train to near-parity with exact cuts."""
    x, y = data
    rng = np.random.default_rng(13)
    mask = rng.random(x.shape[0]) < 0.8
    kw = dict(n_rounds=10, max_depth=4, objective="binary:logistic")
    ext = ExternalDMatrix.from_arrays(x[mask], y[mask], chunk_rows=500)
    dmem = DeviceDMatrix(x[mask], label=y[mask])
    acc = []
    for bst in (Booster(**kw).fit(ext), Booster(**kw).fit(dmem)):
        p = np.asarray(bst.predict(x[~mask])) > 0.5
        acc.append(float(np.mean(p == y[~mask])))
    assert acc[0] > 0.75
    assert abs(acc[0] - acc[1]) < 0.05


def test_paging_and_surfaces(data):
    x, y = data
    ext = ExternalDMatrix.from_arrays(x, y, chunk_rows=1000)
    assert ext.n_rows == x.shape[0]
    assert ext.n_features == x.shape[1]
    assert ext.nbytes_device == 0  # nothing paged in yet
    cpb = ext.packed_bins()
    assert isinstance(cpb, C.ChunkedPackedBins)
    assert ext.nbytes_device == ext.nbytes_host
    assert cpb.padded_rows >= ext.n_rows
    ext.unload()
    assert ext.nbytes_device == 0
    # save/load roundtrip after an external fit
    bst = Booster(n_rounds=4, max_depth=3, objective="binary:logistic").fit(ext)
    import tempfile

    with tempfile.NamedTemporaryFile(suffix=".msgpack") as tmp:
        bst.save(tmp.name)
        loaded = Booster.load(tmp.name)
    np.testing.assert_array_equal(
        np.asarray(loaded.predict(x)), np.asarray(bst.predict(x))
    )


def test_kernel_histograms_rejected_for_external(data):
    x, y = data
    ext = ExternalDMatrix.from_arrays(x, y, chunk_rows=1000)
    bst = Booster(
        n_rounds=2,
        max_depth=3,
        objective="binary:logistic",
        use_kernel_histograms=True,
    )
    with pytest.raises(NotImplementedError, match="kernel"):
        bst.fit(ext)


def test_distributed_external_matches_single_device():
    """The chunk loop composes with shard_map: chunks shard across the mesh
    and the resulting Booster matches single-device external training."""
    import os
    import subprocess
    import sys
    import textwrap

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(root, "src")
    script = textwrap.dedent(
        """
        import numpy as np, jax, jax.numpy as jnp
        from repro.core import Booster, BoosterConfig, ExternalDMatrix
        from repro.dist import make_mesh
        rng = np.random.default_rng(2)
        n, f = 2048, 6
        x = rng.normal(size=(n, f)).astype(np.float32)
        y = (x @ rng.normal(size=f) > 0).astype(np.float32)
        cfg = BoosterConfig(n_rounds=4, max_depth=3,
                            objective="binary:logistic", max_bins=32)
        # 16 chunks of 128 rows -> 2 chunks per shard on an 8-way mesh
        ext = ExternalDMatrix.from_arrays(x, y, chunk_rows=128,
                                          max_bins=32, cuts="exact")
        single = Booster(cfg).fit(ext)
        mesh = make_mesh((8,), ("data",))
        sharded = Booster(cfg).fit(ext, mesh=mesh)
        assert bool(jnp.all(single.ensemble.feature == sharded.ensemble.feature))
        assert bool(jnp.all(single.ensemble.split_bin == sharded.ensemble.split_bin))
        d = float(jnp.max(jnp.abs(single.ensemble.leaf_value
                                  - sharded.ensemble.leaf_value)))
        assert d < 1e-4, d
        # misaligned chunking is rejected with a clear error
        bad = ExternalDMatrix.from_arrays(x[:2000], y[:2000], chunk_rows=300,
                                          max_bins=32)
        try:
            Booster(cfg).fit(bad, mesh=mesh)
        except ValueError as e:
            assert "chunk_rows" in str(e)
        else:
            raise AssertionError("misaligned chunks should be rejected")
        print("EXTERNAL-SHARDED-OK")
        """
    )
    res = subprocess.run(
        [sys.executable, "-c", script],
        capture_output=True,
        text=True,
        timeout=900,
        env=env,
    )
    assert res.returncode == 0, res.stdout + "\n" + res.stderr
    assert "EXTERNAL-SHARDED-OK" in res.stdout


def test_chunked_packed_bins_roundtrip(data):
    """Unpacking each chunk of the stack reproduces the flat bins."""
    x, y = data
    d = DeviceDMatrix(x, label=y)
    ext = ExternalDMatrix.from_arrays(x, y, chunk_rows=700, cuts="exact")
    cpb = ext.packed_bins()
    rows = [
        np.asarray(C.unpack(cpb.packed[c], cpb.bits, cpb.chunk_rows))
        for c in range(cpb.n_chunks)
    ]
    bins_chunked = np.concatenate(rows)[: ext.n_rows]
    np.testing.assert_array_equal(bins_chunked, np.asarray(d.matrix.unpack()))


# --- streamed out-of-core executor (DESIGN.md §17) -------------------------


def _ext(x, y, paging, prefetch=2, chunk_rows=700):
    return ExternalDMatrix.from_arrays(
        x,
        y,
        chunk_rows=chunk_rows,
        cuts="exact",
        paging=paging,
        prefetch_chunks=prefetch,
    )


def test_streamed_fit_bit_identical_resident_and_overlap_off(data):
    """The tentpole guarantee: the streamed executor (async prefetch ring)
    equals the resident compiled-scan fit bit for bit, with the overlap on
    (prefetch_chunks=2) or off (prefetch_chunks=0), and equals the
    in-memory fit on the same cuts."""
    x, y = data
    kw = dict(n_rounds=8, max_depth=4, objective="binary:logistic")
    ext = _ext(x, y, "resident")
    b_res = Booster(**kw).fit(ext)
    b_str = Booster(**kw).fit(_ext(x, y, "stream"))
    b_syn = Booster(**kw).fit(_ext(x, y, "stream", prefetch=0))
    b_mem = Booster(**kw).fit(DeviceDMatrix(x, label=y, cuts=ext.cuts))
    assert_boosters_identical(b_res, b_str)
    assert_boosters_identical(b_str, b_syn)
    assert_boosters_identical(b_str, b_mem)
    np.testing.assert_array_equal(np.asarray(b_res.margins), np.asarray(b_str.margins))
    np.testing.assert_array_equal(
        np.asarray(b_res.predict(x)), np.asarray(b_str.predict(x))
    )


def test_streamed_fit_never_pages_full_stack(data):
    """The point of streaming: device residency stays bounded by the pager
    ring — the full chunk stack is never device-resident."""
    x, y = data
    ext = _ext(x, y, "stream")
    bst = Booster(n_rounds=4, max_depth=3, objective="binary:logistic").fit(ext)
    assert ext.nbytes_device == 0  # no cached device stack after the fit
    assert ext.stream_stats is not None
    assert ext.stream_stats.chunks_paged > 0
    assert ext.stream_stats.rows_touched > 0
    assert bst.n_rounds_trained == 4


def test_streamed_multiclass_and_sampled_bit_identical(data):
    x, _ = data
    rng = np.random.default_rng(11)
    y3 = rng.integers(0, 3, x.shape[0]).astype(np.float32)
    kw = dict(n_rounds=5, max_depth=3, objective="multi:softmax", n_classes=3)
    assert_boosters_identical(
        Booster(**kw).fit(_ext(x, y3, "resident")),
        Booster(**kw).fit(_ext(x, y3, "stream")),
    )
    _, y = data
    kw = dict(
        n_rounds=5,
        max_depth=3,
        objective="binary:logistic",
        subsample=0.6,
        colsample_bytree=0.8,
        seed=3,
    )
    assert_boosters_identical(
        Booster(**kw).fit(_ext(x, y, "resident")),
        Booster(**kw).fit(_ext(x, y, "stream")),
    )


def test_streamed_update_continuation_matches_longer_fit(data):
    """update() over a streamed matrix replays one long fit's key stream
    and margins exactly (resume-safe eager executor)."""
    x, y = data
    kw = dict(n_rounds=8, max_depth=3, objective="binary:logistic")
    long = Booster(**kw).fit(_ext(x, y, "stream"))
    ext = _ext(x, y, "stream")
    short = Booster(n_rounds=5, max_depth=3, objective="binary:logistic").fit(ext)
    short.update(ext, 3)
    assert_boosters_identical(long, short)


def test_streamed_eval_sets_and_early_stopping_match_resident(data):
    x, y = data
    rng = np.random.default_rng(5)
    xv = rng.standard_normal((600, x.shape[1])).astype(np.float32)
    yv = (xv[:, 0] > 0).astype(np.float32)
    boosters = []
    for paging in ("resident", "stream"):
        ext = _ext(x, y, paging, chunk_rows=800)
        dv = DeviceDMatrix(xv, label=yv, ref=ext)
        bst = Booster(n_rounds=30, max_depth=3, objective="binary:logistic")
        boosters.append(bst.fit(ext, evals=[(dv, "valid")], early_stopping_rounds=4))
    b_res, b_str = boosters
    assert b_res.best_iteration == b_str.best_iteration
    assert b_res.history == b_str.history
    assert_boosters_identical(b_res, b_str)


def test_paging_knob_validation_and_auto(data):
    x, y = data
    with pytest.raises(ValueError, match="paging"):
        ExternalDMatrix.from_arrays(x, y, chunk_rows=700, paging="bogus")
    with pytest.raises(ValueError, match="prefetch_chunks"):
        ExternalDMatrix.from_arrays(x, y, chunk_rows=700, prefetch_chunks=-1)
    ext = ExternalDMatrix.from_arrays(x, y, chunk_rows=700)
    assert ext.paging == "auto"
    # CPU backends report no usable memory limit -> proven resident path
    assert ext.resolved_paging() in ("resident", "stream")
    assert _ext(x, y, "stream").resolved_paging() == "stream"
    assert _ext(x, y, "resident").resolved_paging() == "resident"
