"""Fused ensemble traversal — all trees x a row block in one launch.

`core.predict` folds the ensemble with a `lax.scan` over stacked tree
arenas: one scan step per tree, each step a levelwise gather over all rows.
That shape is right *inside* the training round (the round step only ever
applies k trees), but for batch inference over a deep ensemble it serialises
n_trees tiny dispatches of O(rows) work each — on a 500-tree model the
traversal is latency-bound on loop overhead, not on gathers.

The serving path fuses the other axis instead: a levelwise loop advances a
BLOCK of trees over all rows at once. Per level the node state is a
(trees_block, n_rows) int32 plane, and each step costs exactly two gathers:

  * one on a per-tree **stacked routing table** — the arena's SoA fields
    (split feature, comparison threshold, default direction, left/right
    child) interleaved into a single (n_trees, arena, 5) f32 array, so the
    full routing record of a (tree, node) pair lands in one contiguous
    16-byte read instead of five strided gathers (leaves self-loop via
    child pointers and a +inf threshold, absorbing the is-leaf select);
  * one on the input block for the feature value.

Blocks of TREES_BLOCK trees keep the level planes cache-resident — the
whole-(n_trees, n_rows) formulation streams multi-MB temporaries through
memory every level and loses to the scan on CPU — while still collapsing
n_trees scan steps into n_trees / TREES_BLOCK. Work is otherwise identical
to the scan form: the leaf every (tree, row) pair lands in is the same and
the class fold reduces in the same order, so fused outputs are
BIT-IDENTICAL to `core.predict`'s (tested).

Two input modes, as everywhere else (DESIGN.md §2):

  * packed / bin-space — the model carries cut points and the rows arrive
    quantised (DeviceDMatrix, or the engine quantising a float batch):
    thresholds are integer bin ids, the reserved missing bin encodes NaN.
  * raw — float32 rows vs raw-space thresholds, NaN = missing. The only
    mode available to models imported from XGBoost JSON (no cuts attached).

A Pallas TPU kernel of the same computation (one-hot MXU formulation, no
gathers) lives in `kernels.ensemble_traversal`; the functions here are its
parity oracle and the default execution path.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core import predict as PR


TREES_BLOCK = 32  # level planes stay (32, n_rows) — cache-resident on CPU


def _stacked_table(feature, cmp_threshold, default_left, is_leaf):
    """Interleave the routing fields into one (n_trees, arena, 5) f32 table
    so each traversal level pays ONE contiguous gather per (tree, node).

    Columns: [split feature, comparison threshold, default_left, left child,
    right child]. Leaves self-loop (both children point at the leaf itself)
    behind a +inf threshold, so the levelwise step needs no is-leaf select;
    feature/child ids round-trip through f32 exactly (arena and feature
    counts are far below 2^24)."""
    arena = feature.shape[1]
    node_ids = jnp.arange(arena, dtype=jnp.int32)
    cl = jnp.where(is_leaf, node_ids, 2 * node_ids + 1)
    cr = jnp.where(is_leaf, node_ids, 2 * node_ids + 2)
    thr = jnp.where(is_leaf, jnp.inf, cmp_threshold.astype(jnp.float32))
    return jnp.stack(
        [
            feature.astype(jnp.float32), thr,
            default_left.astype(jnp.float32),
            cl.astype(jnp.float32), cr.astype(jnp.float32),
        ],
        axis=-1,
    )


def _blocked_leaves(table, leaf_value, lookup, n_rows: int, max_depth: int):
    """Scan TREES_BLOCK-sized tree blocks through the levelwise loop and
    return the (n_trees, n_rows) leaf-value plane.

    `lookup(f)` maps a (trees_block, n_rows) split-feature plane to
    `(value_f32, is_missing_bool)` planes — the only part that differs
    between raw and bin-space traversal.
    """
    n_trees, arena = leaf_value.shape
    tb = min(TREES_BLOCK, n_trees)
    pad = (-n_trees) % tb
    if pad:  # padding trees self-loop at node 0 and are sliced off below
        table = jnp.pad(table, ((0, pad), (0, 0), (0, 0)))
        leaf_value = jnp.pad(leaf_value, ((0, pad), (0, 0)))
    tables = table.reshape(-1, tb, arena, 5)
    leaf_values = leaf_value.reshape(-1, tb, arena)
    tree_ix = jnp.arange(tb, dtype=jnp.int32)[:, None]  # (tb, 1)

    def one_block(_, blk):
        t5, lv = blk

        def body(__, node):
            with jax.named_scope("route"):
                g = t5[tree_ix, node]  # (tb, N, 5): the level's table gather
                f = g[..., 0].astype(jnp.int32)
            with jax.named_scope("lookup"):
                v, is_missing = lookup(f)
            with jax.named_scope("route"):
                go_left = jnp.where(is_missing, g[..., 2] > 0.5,
                                    v <= g[..., 1])
                return jnp.where(go_left, g[..., 3],
                                 g[..., 4]).astype(jnp.int32)

        node = jnp.zeros((tb, n_rows), jnp.int32)
        node = jax.lax.fori_loop(0, max_depth, body, node)
        with jax.named_scope("leaf"):
            return None, lv[tree_ix, node]

    _, leaves = jax.lax.scan(one_block, None, (tables, leaf_values))
    return leaves.reshape(-1, n_rows)[:n_trees]  # (T, N)


def traverse_ensemble_raw(
    feature, threshold, default_left, leaf_value, is_leaf,
    x: jax.Array, max_depth: int,
) -> jax.Array:
    """(n_trees, n_rows) leaf outputs over float32 rows (NaN = missing)."""
    n_rows = x.shape[0]
    row_ix = jnp.arange(n_rows, dtype=jnp.int32)[None, :]  # (1, N)

    def lookup(f):
        v = x[row_ix, f]  # (tb, N) gather on the row block
        return v, jnp.isnan(v)

    with jax.named_scope("traverse"):
        table = _stacked_table(feature, threshold, default_left, is_leaf)
        return _blocked_leaves(table, leaf_value, lookup, n_rows, max_depth)


def traverse_ensemble_packed(
    feature, split_bin, default_left, leaf_value, is_leaf,
    packed: jax.Array, bits: int, n_rows: int, missing_bin: int,
    max_depth: int,
) -> jax.Array:
    """(n_trees, n_rows) leaf outputs straight from the bit-packed matrix:
    per level, one uint32 word gather per (tree, row) plus a shift/mask —
    the dense bins plane never exists (DESIGN.md §2). Bin ids compare in
    f32 (exact: bins < 2^24), so the stacked table is shared with raw
    mode."""
    from repro.core import compress as C

    spw = C.symbols_per_word(bits)
    mask = jnp.uint32((1 << bits) - 1)

    def lookup(f):
        b = (packed[f, word_ix] >> shift) & mask
        return b.astype(jnp.float32), b == jnp.uint32(missing_bin)

    with jax.named_scope("traverse"):
        row = jnp.arange(n_rows, dtype=jnp.int32)
        word_ix = (row // spw)[None, :]  # (1, N)
        shift = ((row % spw).astype(jnp.uint32) * jnp.uint32(bits))[None, :]
        table = _stacked_table(feature, split_bin, default_left, is_leaf)
        return _blocked_leaves(table, leaf_value, lookup, n_rows, max_depth)


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
    fold) in n_trees / TREES_BLOCK scan steps instead of n_trees.
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
def ensemble_leaves_chunk(
    ens: PR.Ensemble, chunk_words: jax.Array, bits: int, chunk_rows: int,
    n_rows: int, missing_bin: int, max_depth: int,
) -> jax.Array:
    """(n_trees, chunk_rows) leaf outputs of ONE packed chunk — the unit of
    the external-memory paged predict path (`Booster.predict` on an
    `ExternalDMatrix` streams host chunks through this, never materialising
    the full device stack). Every chunk shares one compiled program."""
    del n_rows  # chunks are traversed at their padded chunk_rows size
    return traverse_ensemble_packed(
        ens.feature, ens.split_bin, ens.default_left, ens.leaf_value,
        ens.is_leaf, chunk_words, bits, chunk_rows, missing_bin, max_depth,
    )


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
