"""Fused ensemble traversal — all trees x a row block in one launch.

`core.predict` folds the ensemble with a `lax.scan` over stacked tree
arenas: one scan step per tree, each step a levelwise walk whose every
level gathers one split record and one feature value per row. That shape
is right *inside* the training round (the round step only ever applies k
trees), but for batch inference over a deep ensemble it serialises n_trees
steps, and on the TPU a gather whose indices differ per row costs ~10 ns
an element.

The serving path evaluates a block of trees densely instead, with no
per-row index anywhere. For a block of `tb` trees of depth D, with the
internal slots 0 … 2^D−2 of the arena's implicit heap, level by level:

  * lookup — each slot's split feature over the whole row block: a row
    gather of the transposed rows (raw) or of the packed words, unpacked
    by a broadcast shift and mask (bin space). The indices are per (tree,
    slot), never per row.
  * route  — `go_left = where(missing, default_left, value <= threshold)`
    at each slot, and the path one-hot grows by one level:
    `oh_{d+1} = [oh_d & gl_d, oh_d & ~gl_d]`, to `(2^D, tb, rows)`.
  * leaf   — each bottom slot carries the value of the first leaf on its
    path from the root (`_node_tables`, once a call); the hot slot's value
    is selected and the other slots read −inf, so the max over the slots
    is exactly the value the walk would land on.

Planes are slot-major, `(slots, trees, rows)`: rows fill the lanes, trees
the sublanes, and the one-hot's halves join along the leading axis, whole
tiles at a time (`_path_order`). Block sizes follow from the static shapes
(`_block_sizes`). The leaves are the walk's, bit for bit, and the class
fold is `core.predict`'s own, reading a loop's output as it does there,
so fused margins are BIT-IDENTICAL to the per-tree scan's (tested).

Two input modes, as everywhere else (DESIGN.md §2):

  * packed / bin-space — the model carries cut points and the rows arrive
    quantised (DeviceDMatrix, or the engine quantising a float batch):
    thresholds are integer bin ids, the reserved missing bin encodes NaN.
  * raw — float32 rows vs raw-space thresholds, NaN = missing. The only
    mode available to models imported from XGBoost JSON (no cuts attached).

A Pallas TPU kernel of the raw-mode computation (one-hot MXU formulation)
lives in `kernels.ensemble_traversal`; the functions here are its parity
oracle and the default execution path.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import predict as PR


# A block's (2^D, trees, rows) f32 plane at most. Small planes are what
# make the dense form fast on a v5e: 500 depth-6 trees over 275,000 rows
# take 0.069 s in 8-tree by 8,192-row blocks, 0.113 s by 16,384 rows and
# 0.23 s by all rows (PERF.md).
_PLANE_BYTES = 1 << 24


def _block_sizes(n_trees: int, n_rows: int, max_depth: int) -> tuple[int, int]:
    """(trees, rows) of one block, from the static shapes alone: as many as
    keep a block's (2^max_depth, trees, rows) f32 plane within
    _PLANE_BYTES. Trees come in multiples of 8 (the sublane tile) unless
    half the ensemble is fewer; the rows are split, into even blocks of a
    multiple of 128 rows (the lane tile), only where 8 trees over every row
    would not fit.

    A block holds at most half the trees, so the tree blocks stay a loop:
    the class fold then reads the leaf plane from the loop's output, as
    core.predict's reads its scan's, and the CPU compiler orders the two
    sums alike (it reorders one fused into the leaf step)."""
    cells = (_PLANE_BYTES // 4) >> max_depth  # trees x rows of a block
    tb = min(-(-n_trees // 2), max(8, cells // n_rows // 8 * 8))
    n_rb = -(-n_rows // max(128, cells // tb // 128 * 128))
    return tb, min(n_rows, -(-n_rows // (n_rb * 128)) * 128)


def _path_order(max_depth: int):
    """Heap slots in the order the path one-hot holds them: the internal
    slots level by level, and the bottom level's local indices. Appending
    the right-going half after the left-going one, rather than interleaving
    them, puts the children of the node at position p at p and p + 2^d, so
    the one-hot grows by whole-tile concatenations on the TPU."""
    level, internal = np.zeros(1, np.int32), []
    for d in range(max_depth):
        internal.append(2**d - 1 + level)
        level = np.concatenate([2 * level, 2 * level + 1])
    return np.concatenate(internal), level


def _node_tables(feature, cmp_threshold, default_left, leaf_value, is_leaf,
                 n_features: int, max_depth: int):
    """Slot-major tables of the dense form in `_path_order`, built once a
    call.

    The internal slots' split feature, comparison threshold and default
    direction, (2^D − 1, n_trees) each; out-of-range feature ids (only
    leaves and inactive slots hold them, and no walk decides there) clamp
    to feature 0. And
    (2^D, n_trees) the leaf value the walk ends on below each bottom slot:
    that of the first `is_leaf` slot on the path from the root, else the
    bottom slot's own (the walk stops after max_depth steps)."""
    internal, bottom = _path_order(max_depth)
    f = feature[:, internal]
    f = jnp.where((f >= 0) & (f < n_features), f, 0)
    lv, found = leaf_value[:, :1], is_leaf[:, :1]
    for d in range(1, max_depth + 1):
        lo, hi = 2**d - 1, 2 ** (d + 1) - 1
        up_lv, up_found = jnp.repeat(lv, 2, axis=1), jnp.repeat(found, 2, axis=1)
        lv = jnp.where(up_found, up_lv, leaf_value[:, lo:hi])
        found = up_found | is_leaf[:, lo:hi]
    return (f.T, cmp_threshold[:, internal].T, default_left[:, internal].T,
            lv[:, bottom].T)


def _dense_leaves(tables, data: jax.Array, cols: int, lookup, tb: int,
                  max_depth: int) -> jax.Array:
    """(n_trees padded to tb, rows of every row block) leaf-value plane.

    `data` holds the rows as (n_features, n_row_blocks x cols) columns, and
    `lookup(block, f)` maps a row block's (n_features, cols) slice and a
    level's (slots, tb) split features to its `(value, is_missing)` planes,
    (slots, tb, rows): the only part that differs between raw and bin-space
    traversal."""
    feat, thr, dl, lvb = tables
    pad = (-feat.shape[1]) % tb
    if pad:  # padding trees are sliced off by the caller
        feat, thr, dl, lvb = (jnp.pad(a, ((0, 0), (0, pad)))
                              for a in (feat, thr, dl, lvb))
    blocks = tuple(a.reshape(a.shape[0], -1, tb).swapaxes(0, 1)
                   for a in (feat, thr, dl, lvb))  # (n_blocks, slots, tb)

    def one_tree_block(_, blk):
        f, t, d, lv = blk

        def one_row_block(r):
            block = jax.lax.dynamic_slice_in_dim(data, r * cols, cols, axis=1)
            oh = None
            for lvl in range(max_depth):
                s = slice(2**lvl - 1, 2 ** (lvl + 1) - 1)
                with jax.named_scope("lookup"):
                    v, missing = lookup(block, f[s])
                with jax.named_scope("route"):
                    gl = jnp.where(missing, d[s, :, None], v <= t[s, :, None])
                    oh = jnp.concatenate(
                        [gl, ~gl] if oh is None else [oh & gl, oh & ~gl])
            with jax.named_scope("leaf"):
                # One slot is hot per (tree, row) and the others read -inf:
                # the max is that slot's value, bit for bit.
                return jnp.max(jnp.where(oh, lv[..., None], -jnp.inf), axis=0)

        leaves = jax.lax.map(one_row_block, jnp.arange(data.shape[1] // cols))
        return None, jnp.moveaxis(leaves, 0, 1).reshape(tb, -1)

    _, leaves = jax.lax.scan(one_tree_block, None, blocks)
    return leaves.reshape(-1, leaves.shape[-1])  # (T_pad, rows)


def traverse_ensemble_raw(
    feature, threshold, default_left, leaf_value, is_leaf,
    x: jax.Array, max_depth: int,
) -> jax.Array:
    """(n_trees, n_rows) leaf outputs over float32 rows (NaN = missing)."""
    n_rows, n_features = x.shape
    n_trees = feature.shape[0]
    tb, rb = _block_sizes(n_trees, n_rows, max_depth)
    n_rb = -(-n_rows // rb)

    def lookup(block, f):
        v = block[f]
        return v, jnp.isnan(v)

    with jax.named_scope("traverse"):
        xt = jnp.pad(x.T, ((0, 0), (0, n_rb * rb - n_rows)))  # (F, rows)
        tables = _node_tables(feature, threshold, default_left, leaf_value,
                              is_leaf, n_features, max_depth)
        leaves = _dense_leaves(tables, xt, rb, lookup, tb, max_depth)
        return leaves[:n_trees, :n_rows]


def traverse_ensemble_packed(
    feature, split_bin, default_left, leaf_value, is_leaf,
    packed: jax.Array, bits: int, n_rows: int, missing_bin: int,
    max_depth: int,
) -> jax.Array:
    """(n_trees, n_rows) leaf outputs straight from the bit-packed matrix:
    each slot's feature row of words is gathered whole and unpacked by a
    broadcast shift and mask — the dense bins matrix never exists
    (DESIGN.md §2). Bin ids compare as int32, exactly as the walk's."""
    from repro.core import compress as C

    spw = C.symbols_per_word(bits)
    n_features, n_words = packed.shape
    n_trees = feature.shape[0]
    tb, rb = _block_sizes(n_trees, n_rows, max_depth)
    wb = -(-rb // spw)  # words of a row block
    n_rb = -(-n_words // wb)
    shift = jnp.arange(spw, dtype=jnp.uint32) * jnp.uint32(bits)
    mask = jnp.uint32((1 << bits) - 1)

    def lookup(block, f):
        w = block[f]
        b = ((w[..., None] >> shift) & mask).reshape(w.shape[:-1] + (-1,))
        b = b.astype(jnp.int32)
        return b, b == missing_bin

    with jax.named_scope("traverse"):
        words = jnp.pad(packed, ((0, 0), (0, n_rb * wb - n_words)))
        tables = _node_tables(feature, split_bin, default_left, leaf_value,
                              is_leaf, n_features, max_depth)
        leaves = _dense_leaves(tables, words, wb, lookup, tb, max_depth)
        return leaves[:n_trees, :n_rows]


def _fold(leaves: jax.Array, ens: PR.Ensemble, n_rows: int) -> jax.Array:
    """`core.predict._fold_classes`, named `traverse/fold` in traces."""
    with jax.named_scope("traverse"), jax.named_scope("fold"):
        return PR._fold_classes(leaves, ens, n_rows)


@functools.partial(jax.jit, static_argnames=("max_depth",))
def predict_margins_fused(
    ens: PR.Ensemble, x: jax.Array, max_depth: int
) -> jax.Array:
    """Margins (n_rows, n_classes) from raw float rows, fused over trees.

    Bit-identical to `core.predict.predict_raw` (same leaves, same class
    fold), with no per-row gather.
    """
    leaves = traverse_ensemble_raw(
        ens.feature, ens.threshold, ens.default_left, ens.leaf_value,
        ens.is_leaf, x, max_depth,
    )
    return _fold(leaves, ens, x.shape[0])


@functools.partial(
    jax.jit, static_argnames=("bits", "n_rows", "missing_bin", "max_depth")
)
def predict_margins_fused_packed(
    ens: PR.Ensemble, packed: jax.Array, bits: int, n_rows: int,
    missing_bin: int, max_depth: int,
) -> jax.Array:
    """Margins from the bit-packed quantised matrix, fused over trees —
    bit-identical to `core.predict.predict_binned_packed`."""
    leaves = traverse_ensemble_packed(
        ens.feature, ens.split_bin, ens.default_left, ens.leaf_value,
        ens.is_leaf, packed, bits, n_rows, missing_bin, max_depth,
    )
    return _fold(leaves, ens, n_rows)


@functools.partial(
    jax.jit,
    static_argnames=("bits", "chunk_rows", "n_rows", "missing_bin",
                     "max_depth"),
)
def predict_margins_fused_chunked(
    ens: PR.Ensemble, packed: jax.Array, bits: int, chunk_rows: int,
    n_rows: int, missing_bin: int, max_depth: int,
) -> jax.Array:
    """Fused margins over a device-resident chunk stack (the representation
    an `ExternalDMatrix` that already paged in for training holds) — a scan
    over chunks of the fused per-chunk traversal, bit-identical to
    `core.predict.predict_binned_chunked`."""

    def one_chunk(carry, words):
        return carry, traverse_ensemble_packed(
            ens.feature, ens.split_bin, ens.default_left, ens.leaf_value,
            ens.is_leaf, words, bits, chunk_rows, missing_bin, max_depth,
        )

    _, leaves = jax.lax.scan(one_chunk, None, packed)  # (C, T, chunk_rows)
    leaves = jnp.moveaxis(leaves, 0, 1).reshape(
        leaves.shape[1], -1
    )[:, :n_rows]  # (T, N) in global row order
    return _fold(leaves, ens, n_rows)
