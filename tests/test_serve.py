"""The serving subsystem (DESIGN.md §14): fused ensemble traversal,
PredictEngine shape-bucketed caches, and the streaming ExternalDMatrix
predict path.

The fused traversal's contract is BIT-IDENTITY with core.predict's
per-tree scan (same leaves, same class-fold order) — asserted exactly, not
to tolerance. The engine's contract is zero recompiles across mixed batch
sizes after warmup — asserted with the trace-counter idiom (the counter
bumps at trace time only). The Pallas kernel is validated in interpret
mode against the XLA oracle (matmul accumulation differs, so to
tolerance).
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import Booster, DeviceDMatrix, ExternalDMatrix
from repro.core import predict as PR
from repro.kernels import ref as KREF
from repro.kernels.ops import ensemble_margins_op
from repro.serve import PredictEngine
from repro.serve import traversal as TV


@pytest.fixture(scope="module")
def binary():
    rng = np.random.default_rng(3)
    x = rng.normal(size=(600, 7)).astype(np.float32)
    x[rng.random(x.shape) < 0.12] = np.nan
    y = (np.nan_to_num(x[:, 0]) + np.nan_to_num(x[:, 2])
         + 0.3 * rng.normal(size=600) > 0).astype(np.float32)
    d = DeviceDMatrix(x, label=y, max_bins=64)
    bst = Booster(n_rounds=7, max_depth=4, max_bins=64,
                  objective="binary:logistic", seed=0).fit(d)
    return bst, d, x, y


@pytest.fixture(scope="module")
def multiclass():
    rng = np.random.default_rng(4)
    x = rng.normal(size=(500, 5)).astype(np.float32)
    x[rng.random(x.shape) < 0.1] = np.nan
    y = (np.nan_to_num(x[:, 0]) > 0).astype(np.float32) \
        + (np.nan_to_num(x[:, 1]) > 0.5)
    d = DeviceDMatrix(x, label=y.astype(np.float32), max_bins=32)
    bst = Booster(n_rounds=5, max_depth=3, max_bins=32,
                  objective="multi:softmax", n_classes=3, seed=1).fit(d)
    return bst, d, x


# --- fused traversal: bit-identity with the per-tree scan -------------------

def test_fused_raw_bit_identical(binary):
    bst, _, x, _ = binary
    ens, md = bst.ensemble, bst.ensemble.max_depth
    ref = PR.predict_raw(ens, jnp.asarray(x), md)
    fused = TV.predict_margins_fused(ens, jnp.asarray(x), md)
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(fused))


def test_fused_raw_bit_identical_multiclass(multiclass):
    bst, _, x = multiclass
    ens, md = bst.ensemble, bst.ensemble.max_depth
    ref = PR.predict_raw(ens, jnp.asarray(x), md)
    fused = TV.predict_margins_fused(ens, jnp.asarray(x), md)
    assert ref.shape == (x.shape[0], 3)
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(fused))


def test_fused_packed_bit_identical(binary):
    bst, d, _, _ = binary
    ens, md = bst.ensemble, bst.ensemble.max_depth
    pb = d.matrix.as_packed_bins()
    mb = d.max_bins - 1
    ref = PR.predict_binned_packed(ens, pb.packed, pb.bits, d.n_rows, mb, md)
    fused = TV.predict_margins_fused_packed(
        ens, pb.packed, pb.bits, d.n_rows, mb, md
    )
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(fused))


def test_fused_chunked_bit_identical(binary):
    bst, d, x, y = binary
    ens, md = bst.ensemble, bst.ensemble.max_depth
    ed = ExternalDMatrix.from_arrays(
        x, label=y, chunk_rows=128, max_bins=64, cuts=np.asarray(d.cuts)
    )
    cpb = ed.packed_bins()
    mb = d.max_bins - 1
    ref = PR.predict_binned_chunked(
        ens, cpb.packed, cpb.bits, cpb.chunk_rows, cpb.n_rows, mb, md
    )
    fused = TV.predict_margins_fused_chunked(
        ens, cpb.packed, cpb.bits, cpb.chunk_rows, cpb.n_rows, mb, md
    )
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(fused))


def test_booster_predict_routes_through_fused(binary):
    """Booster.predict on arrays / DeviceDMatrix stays exactly what the
    per-tree scan produced before the fused path replaced it."""
    bst, d, x, _ = binary
    ens, md = bst.ensemble, bst.ensemble.max_depth
    np.testing.assert_array_equal(
        np.asarray(bst.predict_margins(x)),
        np.asarray(PR.predict_raw(ens, jnp.asarray(x), md)),
    )
    pb = d.matrix.as_packed_bins()
    np.testing.assert_array_equal(
        np.asarray(bst.predict_margins(d)),
        np.asarray(PR.predict_binned_packed(
            ens, pb.packed, pb.bits, d.n_rows, d.max_bins - 1, md
        )),
    )


# --- dense form: ragged arenas, exact ties, NaN rows, block sizes ----------

_GRID = np.linspace(-1.5, 1.5, 7).astype(np.float32)


def _ragged_ensemble(rng, n_trees, depth, n_features, n_bins):
    """Random trees whose leaves sit at any depth up to `depth`. Slots below
    a leaf are inactive and, like the leaves, hold out-of-range feature ids
    and junk thresholds and values; thresholds come from a small grid (and
    bin ids from a small range) so rows can sit exactly on them."""
    a = 2 ** (depth + 1) - 1
    active = np.zeros((n_trees, a), bool)
    is_leaf = np.zeros((n_trees, a), bool)
    active[:, 0] = True
    for s in range(a):
        d = (s + 1).bit_length() - 1
        stop = (d == depth) | (rng.random(n_trees) < (0.25 if d else 0.1))
        is_leaf[:, s] = active[:, s] & stop
        if d < depth:
            grow = active[:, s] & ~is_leaf[:, s]
            active[:, 2 * s + 1] = active[:, 2 * s + 2] = grow
    split = active & ~is_leaf
    feature = rng.integers(0, n_features, (n_trees, a))
    feature[~split] = rng.choice([-3, -1, n_features, n_features + 7],
                                 (~split).sum())
    threshold = rng.choice(_GRID, (n_trees, a))
    threshold[~active] = np.nan
    leaf_value = rng.normal(size=(n_trees, a)).astype(np.float32)
    leaf_value[~active] = 1e30
    leaf_value[is_leaf & (rng.random((n_trees, a)) < 0.05)] = -0.0
    return PR.Ensemble(
        feature=jnp.asarray(feature, jnp.int32),
        split_bin=jnp.asarray(rng.integers(0, n_bins - 1, (n_trees, a)),
                              jnp.int32),
        threshold=jnp.asarray(threshold, jnp.float32),
        default_left=jnp.asarray(rng.random((n_trees, a)) < 0.5),
        leaf_value=jnp.asarray(leaf_value),
        is_leaf=jnp.asarray(is_leaf),
        gain=jnp.zeros((n_trees, a), jnp.float32),
    )


def _dense_case(depth, n_rows=1000, n_trees=13, n_features=6, n_bins=16):
    rng = np.random.default_rng(depth)
    ens = _ragged_ensemble(rng, n_trees, depth, n_features, n_bins)
    x = rng.normal(size=(n_rows, n_features)).astype(np.float32)
    on_grid = rng.random(x.shape) < 0.4
    x[on_grid] = rng.choice(_GRID, on_grid.sum())  # ties: v == threshold
    x[rng.random(x.shape) < 0.1] = np.nan
    x[:5] = np.nan  # rows missing everywhere: default directions alone
    bins = rng.integers(0, n_bins - 1, x.shape)
    bins[np.isnan(x)] = n_bins - 1  # the missing bin
    return ens, x, bins


@pytest.mark.parametrize("plane_bytes", [None, 1 << 20],
                         ids=["real_blocks", "small_blocks"])
@pytest.mark.parametrize("mode", ["raw", "packed"])
@pytest.mark.parametrize("depth", [3, 6, 8, 10])
def test_dense_form_bit_identical(depth, mode, plane_bytes, monkeypatch):
    """The dense form against core.predict's per-tree walk, exactly. With a
    small plane budget the 13 trees and 1,000 rows split into ragged tree
    and row blocks; with the real one, into what the shapes give."""
    from repro.core import compress as C

    ens, x, bins = _dense_case(depth)
    n_rows, n_bins, bits = x.shape[0], 16, 5  # 6 rows a word: padded words
    if plane_bytes is not None:
        monkeypatch.setattr(TV, "_PLANE_BYTES", plane_bytes)
        tb, rb = TV._block_sizes(ens.n_trees, n_rows, depth)
        assert ens.n_trees % tb or depth == 3
        assert (rb < n_rows and n_rows % rb) or depth == 3
    if mode == "raw":
        ref = PR.predict_raw(ens, jnp.asarray(x), depth)
        got = jax.jit(lambda e, a: TV._fold(TV.traverse_ensemble_raw(
            e.feature, e.threshold, e.default_left, e.leaf_value,
            e.is_leaf, a, depth), e, n_rows))(ens, jnp.asarray(x))
    else:
        packed = C.pack(jnp.asarray(bins), bits)
        ref = PR.predict_binned_packed(ens, packed, bits, n_rows,
                                       n_bins - 1, depth)
        got = jax.jit(lambda e, p: TV._fold(TV.traverse_ensemble_packed(
            e.feature, e.split_bin, e.default_left, e.leaf_value,
            e.is_leaf, p, bits, n_rows, n_bins - 1, depth), e, n_rows))(
                ens, packed)
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(got))


def test_block_sizes_follow_the_shapes():
    """Rows split into even lane multiples only where 8 trees over every
    row would pass the budget (275,000 rows at depth 6: 34 blocks of 8
    trees by 8,192 rows); small batches take half the trees a block, so
    the blocks stay a loop."""
    assert TV._block_sizes(500, 275_000, 6) == (8, 8_192)
    assert TV._block_sizes(500, 275_000, 10) == (8, 512)
    assert TV._block_sizes(500, 4_096, 6) == (16, 4_096)
    assert TV._block_sizes(500, 16, 6) == (250, 16)
    assert TV._block_sizes(3, 275_000, 3) == (2, 137_600)
    assert TV._block_sizes(1, 100, 6) == (1, 100)
    for t, n, d in [(500, 275_000, 6), (500, 2_200_000, 8), (7, 999, 10)]:
        tb, rb = TV._block_sizes(t, n, d)
        assert 2**d * tb * rb * 4 <= TV._PLANE_BYTES
        assert rb == n or rb % 128 == 0


_GATHER = re.compile(r'"stablehlo\.(?:dynamic_)?gather"\(.*?\) .*?: '
                     r'\(tensor<[^>]*>, tensor<([^>]*)>')


def _gather_index_dims(lowered) -> list[list[int]]:
    return [[int(v) for v in shape.split("x")[:-1]]
            for shape in _GATHER.findall(lowered.as_text())]


@pytest.mark.parametrize("n_rows", [1000, 1500])
def test_no_gather_is_indexed_per_row(binary, n_rows):
    """Neither fused traversal gathers by a per-row index: no gather's index
    operand has a dimension of the row count."""
    from repro.core import compress as C

    bst, d, _, _ = binary
    ens, md = bst.ensemble, bst.ensemble.max_depth
    x = jax.ShapeDtypeStruct((n_rows, 7), jnp.float32)
    words = jax.ShapeDtypeStruct(
        (7, -(-n_rows // C.symbols_per_word(d.bits))), jnp.uint32)
    for lowered in (
        TV.predict_margins_fused.lower(ens, x, md),
        TV.predict_margins_fused_packed.lower(
            ens, words, d.bits, n_rows, d.max_bins - 1, md),
    ):
        dims = _gather_index_dims(lowered)
        assert dims, "no gather found: the pattern no longer reads the HLO"
        assert all(n_rows not in ds for ds in dims), dims


# --- Pallas kernel (interpret mode) -----------------------------------------

def test_kernel_matches_oracle(binary):
    bst, _, x, _ = binary
    ens, md = bst.ensemble, bst.ensemble.max_depth
    got = ensemble_margins_op(
        ens.feature, ens.threshold, ens.default_left, ens.leaf_value,
        ens.is_leaf, jnp.asarray(x), ens.n_classes, md,
    )
    want = KREF.ensemble_margins_ref(
        ens.feature, ens.threshold, ens.default_left, ens.leaf_value,
        ens.is_leaf, jnp.asarray(x), ens.n_classes, md,
    )
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-6
    )


def test_kernel_matches_oracle_multiclass(multiclass):
    bst, _, x = multiclass
    ens, md = bst.ensemble, bst.ensemble.max_depth
    got = ensemble_margins_op(
        ens.feature, ens.threshold, ens.default_left, ens.leaf_value,
        ens.is_leaf, jnp.asarray(x), ens.n_classes, md,
    )
    want = KREF.ensemble_margins_ref(
        ens.feature, ens.threshold, ens.default_left, ens.leaf_value,
        ens.is_leaf, jnp.asarray(x), ens.n_classes, md,
    )
    assert got.shape == (x.shape[0], 3)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-6
    )


def test_kernel_small_block_sizes(binary):
    """Blocking must not change results: odd row counts and tiny blocks
    exercise padding rows (NaN) and padding trees (zero class weight)."""
    from repro.kernels.ensemble_traversal import ensemble_margins_kernel

    bst, _, x, _ = binary
    ens, md = bst.ensemble, bst.ensemble.max_depth
    got = ensemble_margins_kernel(
        ens.feature, ens.threshold, ens.default_left, ens.leaf_value,
        ens.is_leaf, jnp.asarray(x[:193]), ens.n_classes, md,
        trees_blk=4, rows_blk=64,
    )
    want = KREF.ensemble_margins_ref(
        ens.feature, ens.threshold, ens.default_left, ens.leaf_value,
        ens.is_leaf, jnp.asarray(x[:193]), ens.n_classes, md,
    )
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-6
    )


# --- iteration_range / output_margin ----------------------------------------

def test_iteration_range_default_is_full_model(binary):
    bst, _, x, _ = binary
    np.testing.assert_array_equal(
        np.asarray(bst.predict_margins(x, iteration_range=(0, 0))),
        np.asarray(bst.predict_margins(x)),
    )


def test_iteration_range_staged_sum(binary):
    """Margins over [0,a) and [a,n) sum to the full model (one base_score)."""
    bst, _, x, _ = binary
    full = np.asarray(bst.predict_margins(x))
    head = np.asarray(bst.predict_margins(x, iteration_range=(0, 3)))
    tail = np.asarray(bst.predict_margins(x, iteration_range=(3, 0)))
    np.testing.assert_allclose(
        head + tail - bst.base_score, full, rtol=1e-5, atol=1e-6
    )


def test_iteration_range_multiclass_slices_rounds_not_trees(multiclass):
    bst, _, x = multiclass
    m = bst.predict_margins(x, iteration_range=(0, 2))
    assert m.shape == (x.shape[0], 3)
    sliced = PR.slice_rounds(bst.ensemble, 0, 2)
    assert sliced.n_trees == 2 * 3


def test_iteration_range_invalid_raises(binary):
    bst, _, x, _ = binary
    with pytest.raises(ValueError, match="iteration_range"):
        bst.predict_margins(x, iteration_range=(5, 3))
    with pytest.raises(ValueError, match="iteration_range"):
        bst.predict_margins(x, iteration_range=(0, 99))


def test_output_margin_matches_margins(binary):
    bst, _, x, _ = binary
    np.testing.assert_array_equal(
        np.asarray(bst.predict(x, output_margin=True)),
        np.asarray(bst.predict_margins(x)),
    )
    p = np.asarray(bst.predict(x))
    assert p.min() >= 0.0 and p.max() <= 1.0  # sigmoid applied


# --- ExternalDMatrix streaming predict --------------------------------------

def test_external_predict_streams_without_full_page_in(binary):
    """The satellite bugfix: predict on a paged-out ExternalDMatrix must
    stream chunk-by-chunk — never materialising the full device stack —
    and stay bit-identical to the DeviceDMatrix answer."""
    bst, d, x, y = binary
    ed = ExternalDMatrix.from_arrays(
        x, label=y, chunk_rows=150, max_bins=64, cuts=np.asarray(d.cuts)
    )
    assert ed.nbytes_device == 0
    got = bst.predict_margins(ed)
    assert ed.nbytes_device == 0, "predict paged in the full chunk stack"
    np.testing.assert_array_equal(
        np.asarray(got), np.asarray(bst.predict_margins(d))
    )


def test_external_predict_uses_resident_stack_when_paged_in(binary):
    bst, d, x, y = binary
    ed = ExternalDMatrix.from_arrays(
        x, label=y, chunk_rows=150, max_bins=64, cuts=np.asarray(d.cuts)
    )
    ed.packed_bins()  # training-style page-in
    assert ed.nbytes_device > 0
    np.testing.assert_array_equal(
        np.asarray(bst.predict_margins(ed)),
        np.asarray(bst.predict_margins(d)),
    )


# --- PredictEngine ----------------------------------------------------------

def test_engine_no_recompile_across_mixed_batches(binary):
    bst, _, x, _ = binary
    eng = PredictEngine(bst, buckets=(32, 128, 512)).warmup()
    before = eng.trace_count
    assert before == 3  # one trace per bucket
    for n in (1, 7, 32, 33, 100, 128, 129, 300, 512, 600):
        out = eng.predict(x[:n] if n <= len(x)
                          else np.vstack([x, x[: n - len(x)]]))
        assert out.shape[0] == n
    assert eng.trace_count == before, "mixed batch sizes recompiled"


def test_engine_matches_booster_predict(binary):
    bst, _, x, _ = binary
    eng = PredictEngine(bst)
    for n in (1, 5, 300, 600):
        np.testing.assert_array_equal(
            eng.predict(x[:n]), np.asarray(bst.predict(x[:n]))
        )


def test_engine_output_margin_and_iteration_range(binary):
    bst, _, x, _ = binary
    eng = PredictEngine(bst, output_margin=True, iteration_range=(0, 3))
    np.testing.assert_array_equal(
        eng.predict(x),
        np.asarray(bst.predict_margins(x, iteration_range=(0, 3))),
    )


def test_engine_oversized_batch_slices(binary):
    bst, _, x, _ = binary
    eng = PredictEngine(bst, buckets=(64, 256))
    big = np.vstack([x, x])  # 1200 rows > top bucket 256
    np.testing.assert_array_equal(
        eng.predict(big), np.asarray(bst.predict(big))
    )


def test_engine_multiclass_class_ids(multiclass):
    bst, _, x = multiclass
    eng = PredictEngine(bst)
    np.testing.assert_array_equal(eng.predict(x), np.asarray(bst.predict(x)))


def test_engine_validation(binary):
    bst, _, x, _ = binary
    eng = PredictEngine(bst)
    with pytest.raises(ValueError, match="2-D"):
        eng.predict(x[0])
    with pytest.raises(ValueError, match="features"):
        eng.predict(x[:, :3])
    with pytest.raises(ValueError, match="0 rows"):
        eng.predict(x[:0])
    bad = x[:4].copy()
    bad[0, 0] = np.inf
    with pytest.raises(ValueError, match="infinite feature values"):
        eng.predict(bad)
    # NaN stays the legal missing marker.
    ok = x[:4].copy()
    ok[0, 0] = np.nan
    assert eng.predict(ok).shape[0] == 4


def test_engine_nan_padding_is_inert(binary):
    """Bucket padding rows are NaN; they must not perturb real rows (each
    row's traversal is independent, asserted by exact equality between a
    padded 5-row call and the direct unpadded predict)."""
    bst, _, x, _ = binary
    eng = PredictEngine(bst, buckets=(512,))
    np.testing.assert_array_equal(
        eng.predict(x[:5]), np.asarray(bst.predict(x[:5]))
    )


def test_engine_stats_accounting(binary):
    bst, _, x, _ = binary
    eng = PredictEngine(bst, buckets=(64,))
    eng.predict(x[:10])  # pays the trace
    for _ in range(5):
        eng.predict(x[:10])
    s = eng.stats()
    assert s["n_calls"] == 5  # compile call excluded
    assert s["rows"] == 50
    assert s["p50_ms"] > 0 and s["p99_ms"] >= s["p50_ms"]
    assert s["rows_per_s"] > 0
    assert eng.stats(include_warmup=True)["n_calls"] == 6
    eng.reset_stats()
    assert eng.stats() == {"n_calls": 0}


def test_engine_requires_fitted_booster():
    with pytest.raises(RuntimeError, match="fitted"):
        PredictEngine(Booster())


def test_sklearn_serve_parity_and_no_recompile():
    from repro.sklearn import XGBClassifier

    rng = np.random.default_rng(9)
    x = rng.normal(size=(400, 5)).astype(np.float32)
    y = (x[:, 0] > 0).astype(int)
    plain = XGBClassifier(n_estimators=5, max_depth=3).fit(x, y)
    served = XGBClassifier(n_estimators=5, max_depth=3, serve=True).fit(x, y)
    np.testing.assert_array_equal(served.predict(x), plain.predict(x))
    np.testing.assert_array_equal(
        served.predict_proba(x), plain.predict_proba(x)
    )
    sizes = (3, 50, 200, 399)
    for n in sizes:  # first pass warms each bucket
        served.predict(x[:n])
    eng = served._serve_engine(output_margin=True)
    before = eng.trace_count
    for n in sizes:  # steady state: no recompiles
        served.predict(x[:n])
    assert eng.trace_count == before
