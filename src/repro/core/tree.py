"""Decision tree construction (paper §2.3, Algorithm 1), jit-compatible form.

Algorithm 1 grows via a dynamic expand queue; data-dependent tree shapes
cannot be traced, so we grow *level-synchronously* into a fixed arena of
2^(max_depth+1) - 1 node slots (DESIGN.md §7.3). All nodes of a level are
histogrammed in ONE fused build (the level-local node id joins the scatter
index), which also batches the AllReduce — one collective per level instead
of one per expand-queue entry (a beyond-paper win recorded in EXPERIMENTS.md).

Growth strategies (the paper: "reconfigurable to prioritise expanding nodes
with a higher reduction in the objective function or nodes closer to the
root"):
  * "depthwise"  — expand every node whose best gain > 0 (closer-to-root
    priority is implied by level order);
  * "lossguide"  — a max_leaves budget; within each level only the top-k
    gains split, k = remaining leaf budget (gain-priority emulation).

`axis_name`: when set, histograms are partial (this shard's rows) and are
combined with jax.lax.psum — the paper's NCCL AllReduceHistograms.
`extra_axes`: further mesh axes to reduce over (e.g. ("pod",)).
"""
from __future__ import annotations

from typing import NamedTuple, Sequence

import jax
import jax.numpy as jnp

from repro.core import compress as C
from repro.core import histogram as H
from repro.core import partition as P
from repro.core import sampling as SMP
from repro.core import split as S


class Tree(NamedTuple):
    """Array-form tree arena (all arrays length 2^(max_depth+1) - 1)."""

    feature: jax.Array  # int32
    split_bin: jax.Array  # int32 (bin-space threshold: bin <= split_bin -> left)
    threshold: jax.Array  # float32 (raw-space threshold: x <= threshold -> left)
    default_left: jax.Array  # bool
    leaf_value: jax.Array  # float32
    is_leaf: jax.Array  # bool
    gain: jax.Array  # float32 (split gain; for importances)

    @property
    def n_arena(self) -> int:
        return self.feature.shape[0]


def arena_size(max_depth: int) -> int:
    return 2 ** (max_depth + 1) - 1


def level_offset(level: int) -> int:
    return 2**level - 1


def grow_tree(
    bins: jax.Array | C.PackedBins | C.ChunkedPackedBins,  # dense rows OR packed
    gh: jax.Array,  # (n, 2) float32
    cuts: jax.Array,  # (f, n_cuts) float32
    max_depth: int,
    max_bins: int,
    params: S.SplitParams = S.SplitParams(),
    growth: str = "depthwise",
    max_leaves: int = 0,  # only used by lossguide
    axis_name: str | None = None,
    extra_axes: Sequence[str] = (),
    feature_axis: str | None = None,
    hist_builder=None,  # optional kernel-backed builder (kernels.ops)
    hist_block_rows: int = 65536,  # packed fallback's dense-tile bound
    hist_subtraction: bool = True,  # smaller-child build + sibling = parent - child
    ctx: SMP.TreeContext | None = None,  # stochastic/constrained growth
    collective=None,  # repro.dist.Collective reduction strategy
) -> Tree:
    """When `bins` is a compress.PackedBins, the tree grows *packed-native*
    (DESIGN.md §2): histograms are built straight from the uint32 words
    (Pallas kernel or the row-block-scan XLA fallback) and row routing
    extracts the split-feature column on the fly — the dense (n, f) bins
    matrix is never materialised. A custom `hist_builder` receives whatever
    representation grow_tree was given.

    When `feature_axis` is set (beyond-paper mode, DESIGN.md §3): `bins`
    and `cuts` hold only this shard's feature slice; histograms stay
    feature-local (1/p of the paper's AllReduce bytes move over the wire),
    splits are evaluated feature-locally and the winner is chosen via an
    all-gather of tiny per-node best-split records; row routing for a split
    owned by another shard arrives via a psum'd route vector.

    `ctx` (DESIGN.md §12) threads per-tree stochastic state: when
    `ctx.row_ids` is set, `gh` is the gathered (m, 2) buffer and the whole
    construction — histograms (via the compacted `*_rows` builders, the
    subtraction trick composed on top), routing, node sums — runs in
    buffer space, so a subsampled round does proportionally less scatter
    work while the dense matrix still never materialises. Feature masks
    (per tree/level/node) and monotone bounds are applied in
    split.evaluate_splits; bounds propagate down the arena. `ctx=None`
    compiles to the exact pre-stochastic program."""
    if collective is not None:
        # A dist.Collective owns the reduction topology (and optional
        # payload compression); its mesh axes drive the same sharded-growth
        # gating as plain axis_name (no subtraction trick, masked-mode
        # subsampling only).
        axis_name, extra_axes = collective.axes[0], collective.axes[1:]
    packed_mode = isinstance(bins, C.PackedBins)
    chunked_mode = isinstance(bins, C.ChunkedPackedBins)
    # Streamed out-of-core bins (core/stream.py) are duck-typed: they are
    # not a traceable pytree (they own a Python chunk pager), so grow_tree
    # must be running EAGERLY to use them — the stream runner guarantees
    # that. Dispatch by attribute to avoid a tree -> stream import cycle.
    streamed_mode = bool(getattr(bins, "is_streamed", False))
    if streamed_mode and (axis_name is not None or collective is not None
                          or feature_axis is not None
                          or hist_builder is not None):
        raise NotImplementedError(
            "streamed out-of-core growth is single-shard with the default "
            "builders; use resident paging for sharded or kernel fits"
        )
    if packed_mode or chunked_mode or streamed_mode:
        if feature_axis is not None:
            raise NotImplementedError(
                "feature-sharded growth requires dense bins (unpack per shard)"
            )
        n, f = bins.n_rows, bins.n_features
    else:
        n, f = bins.shape
    na = arena_size(max_depth)
    missing_bin = max_bins - 1

    stoch = ctx.params if ctx is not None else None
    row_ids = ctx.row_ids if ctx is not None else None
    sampled = row_ids is not None
    if sampled:
        if hist_builder is not None:
            raise NotImplementedError(
                "custom/kernel hist builders are not row-subset aware; use "
                "masked-mode subsampling (ctx.row_ids=None) with them"
            )
        if feature_axis is not None or axis_name is not None:
            raise NotImplementedError(
                "sharded growth uses masked-mode subsampling "
                "(ctx.row_ids=None); compact buffers are single-shard only"
            )
        if not (packed_mode or chunked_mode or streamed_mode):
            # Dense path: gather the sampled view once, then grow as usual.
            bins = bins[row_ids]
            row_ids, sampled = None, False
        n = gh.shape[0]  # buffer size m — positions/compaction live here
    mono_on = stoch is not None and stoch.monotone_on
    if mono_on:
        if len(stoch.monotone) != f:
            raise ValueError(
                f"monotone constraints cover {len(stoch.monotone)} features "
                f"but the matrix has {f}"
            )
        mono_arr = jnp.asarray(stoch.monotone, jnp.int32)
        lower = jnp.full(na, -jnp.inf, jnp.float32)
        upper = jnp.full(na, jnp.inf, jnp.float32)

    if hist_builder is not None:
        if chunked_mode:
            raise NotImplementedError(
                "custom/kernel hist builders are not chunk-aware; use the "
                "default builders for external-memory training"
            )
        build = hist_builder
    elif sampled and streamed_mode:
        def build(sb, gh_, pos_, n_nodes_, max_bins_):
            return sb.build_histograms_rows(gh_, pos_, row_ids, n_nodes_,
                                            max_bins_)
    elif streamed_mode:
        def build(sb, gh_, pos_, n_nodes_, max_bins_):
            return sb.build_histograms(gh_, pos_, n_nodes_, max_bins_)
    elif sampled and chunked_mode:
        def build(cpb, gh_, pos_, n_nodes_, max_bins_):
            return H.build_histograms_chunked_rows(
                cpb.packed, gh_, pos_, row_ids, n_nodes_, max_bins_,
                cpb.bits, cpb.chunk_rows, block_rows=hist_block_rows,
            )
    elif sampled:
        def build(pb, gh_, pos_, n_nodes_, max_bins_):
            return H.build_histograms_packed_rows(
                pb.packed, gh_, pos_, row_ids, n_nodes_, max_bins_,
                pb.bits, block_rows=hist_block_rows,
            )
    elif chunked_mode:
        def build(cpb, gh_, pos_, n_nodes_, max_bins_):
            return H.build_histograms_chunked(
                cpb.packed, gh_, pos_, n_nodes_, max_bins_,
                cpb.bits, cpb.chunk_rows, cpb.n_rows,
            )
    elif packed_mode:
        def build(pb, gh_, pos_, n_nodes_, max_bins_):
            return H.build_histograms_packed(
                pb.packed, gh_, pos_, n_nodes_, max_bins_,
                pb.bits, pb.n_rows, block_rows=hist_block_rows,
            )
    else:
        build = H.build_histograms

    feature = jnp.zeros(na, jnp.int32)
    split_bin = jnp.zeros(na, jnp.int32)
    default_left = jnp.zeros(na, bool)
    leaf_value = jnp.zeros(na, jnp.float32)
    is_leaf = jnp.zeros(na, bool)
    gain_arr = jnp.full(na, -jnp.inf, jnp.float32)
    node_sum = jnp.zeros((na, 2), jnp.float32)

    positions = jnp.zeros(n, jnp.int32)  # all rows start at the root
    with jax.named_scope("split"):
        root_sum = jnp.sum(gh, axis=0)
    with jax.named_scope("allreduce"):
        if collective is not None:
            root_sum = collective.allreduce(root_sum)
        elif axis_name is not None:
            root_sum = jax.lax.psum(root_sum, (axis_name, *extra_axes))
    node_sum = node_sum.at[0].set(root_sum)
    active = jnp.zeros(na, bool).at[0].set(True)
    # lossguide leaf budget: a tree starts as 1 leaf; each split adds 1.
    budget = jnp.asarray(max(max_leaves - 1, 0) if growth == "lossguide" else na)

    # Histogram-subtraction trick (DESIGN.md §7.5): below the root, build
    # histograms only for each parent's smaller child (by instance count)
    # over a compacted n//2 row buffer, and derive the sibling as
    # parent_hist - child_hist. Needs single-shard rows and the default
    # builders (a kernel builder keeps full per-level builds).
    use_subtraction = (
        hist_subtraction
        and hist_builder is None
        and axis_name is None
        and feature_axis is None
    )
    hist_prev = None

    for level in range(max_depth):
        with jax.named_scope(f"level{level}"):
            off = level_offset(level)
            n_nodes = 2**level

            with jax.named_scope("histogram"):
                # --- BuildPartialHistograms (per-shard rows) -------------
                local = jnp.where(
                    (positions >= off) & (positions < off + n_nodes),
                    positions - off,
                    n_nodes,
                ).astype(jnp.int32)
                if use_subtraction and level > 0:
                    hist = _histograms_by_subtraction(
                        bins, gh, local, hist_prev, n_nodes, max_bins,
                        hist_block_rows, row_ids=row_ids,
                    )
                else:
                    hist = build(bins, gh, local, n_nodes, max_bins)
            # --- AllReduceHistograms (paper: NCCL; here: psum, or a
            # dist.Collective strategy with optional compressed payload).
            # The subtraction trick runs on one shard's rows only, so it
            # never reaches a reduction.
            with jax.named_scope("allreduce"):
                if collective is not None:
                    hist = collective.allreduce_hist(hist)
                elif axis_name is not None:
                    hist = jax.lax.psum(hist, (axis_name, *extra_axes))
            hist_prev = hist
            with jax.named_scope("split"):
                # --- EvaluateSplit (prefix-sum scan over bins) -----------
                parent = jax.lax.dynamic_slice_in_dim(node_sum, off, n_nodes)
                feature_mask = (
                    SMP.level_feature_mask(ctx, level, n_nodes, f)
                    if ctx is not None else None
                )
                if mono_on:
                    lvl_lo = jax.lax.dynamic_slice_in_dim(lower, off, n_nodes)
                    lvl_hi = jax.lax.dynamic_slice_in_dim(upper, off, n_nodes)
                    bounds = jnp.stack([lvl_lo, lvl_hi], axis=-1)
                    sp = S.evaluate_splits(
                        hist, parent, params, feature_mask=feature_mask,
                        monotone=mono_arr, node_bounds=bounds,
                    )
                else:
                    sp = S.evaluate_splits(hist, parent, params,
                                           feature_mask=feature_mask)
                if feature_axis is not None:
                    sp = _combine_feature_shards(sp, f, feature_axis)

                lvl_active = jax.lax.dynamic_slice_in_dim(active, off, n_nodes)
                will_split = lvl_active & (sp.gain > 0.0) & jnp.isfinite(sp.gain)

                if growth == "lossguide":
                    # Keep only the top-`budget` gains among would-be splits.
                    g = jnp.where(will_split, sp.gain, -jnp.inf)
                    order = jnp.argsort(-g)  # descending
                    rank = jnp.zeros(n_nodes, jnp.int32).at[order].set(
                        jnp.arange(n_nodes, dtype=jnp.int32)
                    )
                    will_split = will_split & (rank < budget)
                    budget = budget - jnp.sum(will_split)

                idx = off + jnp.arange(n_nodes)
                feature = feature.at[idx].set(jnp.where(will_split, sp.feature, 0))
                split_bin = split_bin.at[idx].set(jnp.where(will_split, sp.split_bin, 0))
                default_left = default_left.at[idx].set(will_split & sp.default_left)
                gain_arr = gain_arr.at[idx].set(jnp.where(will_split, sp.gain, -jnp.inf))
                is_leaf = is_leaf.at[idx].set(lvl_active & ~will_split)
                lvl_leaf = S.leaf_value(parent, params.reg_lambda)
                if mono_on:  # leaf weights respect the inherited bounds
                    lvl_leaf = jnp.clip(lvl_leaf, lvl_lo, lvl_hi)
                leaf_value = leaf_value.at[idx].set(
                    jnp.where(lvl_active & ~will_split, lvl_leaf, 0.0)
                )

                # Children bookkeeping (sums come from the split evaluation — no
                # extra pass over the data, mirroring the paper's histogram reuse).
                lidx, ridx = 2 * idx + 1, 2 * idx + 2
                node_sum = node_sum.at[lidx].set(jnp.where(will_split[:, None], sp.left_sum, 0.0))
                node_sum = node_sum.at[ridx].set(jnp.where(will_split[:, None], sp.right_sum, 0.0))
                active = active.at[lidx].set(will_split).at[ridx].set(will_split)

                if mono_on:
                    # Monotone bound propagation (XGBoost's scheme): the midpoint of
                    # the clipped child weights becomes the dividing bound on the
                    # constrained side; the other side inherits the parent's bound.
                    wl = jnp.clip(S.leaf_value(sp.left_sum, params.reg_lambda),
                                  lvl_lo, lvl_hi)
                    wr = jnp.clip(S.leaf_value(sp.right_sum, params.reg_lambda),
                                  lvl_lo, lvl_hi)
                    mid = 0.5 * (wl + wr)
                    csign = mono_arr[sp.feature]
                    l_lo = jnp.where(csign < 0, mid, lvl_lo)
                    l_hi = jnp.where(csign > 0, mid, lvl_hi)
                    r_lo = jnp.where(csign > 0, mid, lvl_lo)
                    r_hi = jnp.where(csign < 0, mid, lvl_hi)
                    keep = ~will_split
                    lower = lower.at[lidx].set(jnp.where(keep, -jnp.inf, l_lo))
                    lower = lower.at[ridx].set(jnp.where(keep, -jnp.inf, r_lo))
                    upper = upper.at[lidx].set(jnp.where(keep, jnp.inf, l_hi))
                    upper = upper.at[ridx].set(jnp.where(keep, jnp.inf, r_hi))

            with jax.named_scope("repartition"):
                # --- RepartitionInstances --------------------------------
                split_mask = jnp.zeros(na, bool).at[idx].set(will_split)
                full_feature = jnp.zeros(na, jnp.int32).at[idx].set(feature[idx])
                full_bin = jnp.zeros(na, jnp.int32).at[idx].set(split_bin[idx])
                full_dl = jnp.zeros(na, bool).at[idx].set(default_left[idx])
                if sampled and streamed_mode:
                    positions = bins.update_positions_rows(
                        positions, split_mask, full_feature, full_bin, full_dl,
                        missing_bin, row_ids,
                    )
                elif streamed_mode:
                    positions = bins.update_positions(
                        positions, split_mask, full_feature, full_bin, full_dl,
                        missing_bin,
                    )
                elif sampled and chunked_mode:
                    positions = P.update_positions_chunked_rows(
                        bins.packed, positions, split_mask, full_feature, full_bin,
                        full_dl, missing_bin, bins.bits, bins.chunk_rows, row_ids,
                    )
                elif sampled:
                    positions = P.update_positions_packed_rows(
                        bins.packed, positions, split_mask, full_feature, full_bin,
                        full_dl, missing_bin, bins.bits, row_ids,
                    )
                elif chunked_mode:
                    positions = P.update_positions_chunked(
                        bins.packed, positions, split_mask, full_feature, full_bin,
                        full_dl, missing_bin, bins.bits, bins.chunk_rows, bins.n_rows,
                    )
                elif packed_mode:
                    positions = P.update_positions_packed(
                        bins.packed, positions, split_mask, full_feature, full_bin,
                        full_dl, missing_bin, bins.bits,
                    )
                elif feature_axis is None:
                    positions = P.update_positions(
                        bins, positions, split_mask, full_feature, full_bin, full_dl,
                        missing_bin,
                    )
                else:
                    positions = _update_positions_feature_sharded(
                        bins, positions, split_mask, full_feature, full_bin, full_dl,
                        missing_bin, f, feature_axis,
                    )

    with jax.named_scope("split"):
        # Final level: every still-active node is a leaf.
        off = level_offset(max_depth)
        n_nodes = 2**max_depth
        idx = off + jnp.arange(n_nodes)
        lvl_active = jax.lax.dynamic_slice_in_dim(active, off, n_nodes)
        parent = jax.lax.dynamic_slice_in_dim(node_sum, off, n_nodes)
        is_leaf = is_leaf.at[idx].set(lvl_active)
        final_leaf = S.leaf_value(parent, params.reg_lambda)
        if mono_on:
            final_leaf = jnp.clip(
                final_leaf,
                jax.lax.dynamic_slice_in_dim(lower, off, n_nodes),
                jax.lax.dynamic_slice_in_dim(upper, off, n_nodes),
            )
        leaf_value = leaf_value.at[idx].set(
            jnp.where(lvl_active, final_leaf, 0.0)
        )

        # Raw-space thresholds for prediction on unquantised inputs.
        if feature_axis is None:
            threshold = cuts[feature, jnp.clip(split_bin, 0, cuts.shape[1] - 1)]
        else:
            my = jax.lax.axis_index(feature_axis)
            f_loc = jnp.clip(feature - my * f, 0, f - 1)
            owned = (feature // f) == my
            thr_local = cuts[f_loc, jnp.clip(split_bin, 0, cuts.shape[1] - 1)]
            threshold = jax.lax.psum(jnp.where(owned, thr_local, 0.0), feature_axis)
        threshold = jnp.where(is_leaf, jnp.inf, threshold)

    return Tree(
        feature=feature,
        split_bin=split_bin,
        threshold=threshold,
        default_left=default_left,
        leaf_value=leaf_value,
        is_leaf=is_leaf,
        gain=gain_arr,
    )


def _histograms_by_subtraction(
    bins: jax.Array | C.PackedBins,
    gh: jax.Array,
    local: jax.Array,  # (n,) level-local child index, n_nodes = inactive
    hist_prev: jax.Array,  # (n_nodes/2, f, max_bins, 2) parents' full hist
    n_nodes: int,
    max_bins: int,
    hist_block_rows: int,
    row_ids: jax.Array | None = None,  # sampled mode: slot -> global row id
) -> jax.Array:
    """Level histogram via the subtraction trick (DESIGN.md §7.5).

    Per parent, only the smaller child (by instance count) is histogrammed;
    its sibling is parent - child. Since sum_p min(left_p, right_p) <=
    floor(n/2), a static n//2 compaction buffer always suffices — the
    scatter work of every level below the root is halved, which is the
    dominant cost of a boosting round on scatter-bound backends.

    With `row_ids` (subsampled growth, DESIGN.md §12) everything above runs
    in buffer space — `gh`/`local` are (m,)-shaped, the compaction buffer is
    m//2 — and only the word gathers translate slots to global rows.
    """
    packed_mode = isinstance(bins, C.PackedBins)
    chunked_mode = isinstance(bins, C.ChunkedPackedBins)
    n = gh.shape[0]
    n_par = n_nodes // 2
    m = n // 2

    # Instance counts per child -> smaller-child bit per parent (ties: left).
    cnt = jnp.zeros(n_nodes + 1, jnp.int32).at[local].add(1)
    small_bit = (cnt[1:n_nodes:2] < cnt[0:n_nodes:2]).astype(jnp.int32)

    is_active = local < n_nodes
    par = jnp.minimum(local >> 1, n_par - 1)
    sel = is_active & ((local & 1) == small_bit[par])

    # Compact selected row ids into the n//2 buffer (sentinel n = padding).
    order = jnp.cumsum(sel) - 1
    buf = jnp.full(m, n, jnp.int32).at[
        jnp.where(sel, order, m)
    ].set(jnp.arange(n, dtype=jnp.int32), mode="drop")
    parent_ext = jnp.concatenate(
        [jnp.where(sel, par, n_par).astype(jnp.int32),
         jnp.full((1,), n_par, jnp.int32)]
    )
    pos_c = parent_ext[jnp.minimum(buf, n)]
    gh_c = gh[jnp.minimum(buf, n - 1)]
    # Buffer slots -> rows for the word gathers (padding slots carry a real
    # row id but their pos is the dump slot, so they contribute nothing).
    rid_c = buf if row_ids is None else row_ids[jnp.minimum(buf, n - 1)]

    if getattr(bins, "is_streamed", False):
        # buf is ascending (selected rows in row order, sentinels at the
        # tail), so rid_c is ascending too — the streamed builder's
        # per-chunk segmentation requirement. Sentinel slots route to the
        # dump position and contribute nothing wherever they land.
        hist_small = bins.build_histograms_rows(gh_c, pos_c, rid_c, n_par,
                                                max_bins)
    elif chunked_mode:
        hist_small = H.build_histograms_chunked_rows(
            bins.packed, gh_c, pos_c, rid_c, n_par, max_bins, bins.bits,
            bins.chunk_rows, block_rows=hist_block_rows,
        )
    elif packed_mode:
        hist_small = H.build_histograms_packed_rows(
            bins.packed, gh_c, pos_c, rid_c, n_par, max_bins, bins.bits,
            block_rows=hist_block_rows,
        )
    else:
        bins_c = bins[jnp.minimum(buf, n - 1)]
        hist_small = H.build_histograms(bins_c, gh_c, pos_c, n_par, max_bins)

    other = hist_prev - hist_small
    built_left = (small_bit == 0)[:, None, None, None]
    left = jnp.where(built_left, hist_small, other)
    right = jnp.where(built_left, other, hist_small)
    f = hist_prev.shape[1]
    return jnp.stack([left, right], axis=1).reshape(n_nodes, f, max_bins, 2)


def _combine_feature_shards(sp: S.Splits, f_local: int, feature_axis: str) -> S.Splits:
    """Pick the global best split from feature-shard-local bests.

    All-gathers only the per-node best-split records (a few bytes per node)
    instead of full histograms — this is the collective-term optimisation
    measured in EXPERIMENTS.md §Perf. Tie-break matches the single-shard
    global argmax (lowest global feature id wins).
    """
    my = jax.lax.axis_index(feature_axis)
    sp = sp._replace(feature=sp.feature + my * f_local)
    g = jax.lax.all_gather(sp, feature_axis)  # every leaf gains axis 0 (p,)
    win = jnp.argmax(g.gain, axis=0)  # (n_nodes,) first max = lowest shard

    def take(arr):
        w = win.reshape(win.shape + (1,) * (arr.ndim - 1 - win.ndim))
        return jnp.take_along_axis(arr, w[None], axis=0)[0]

    return S.Splits(*(take(x) for x in g))


def _update_positions_feature_sharded(
    bins: jax.Array,
    positions: jax.Array,
    split_mask: jax.Array,
    feature: jax.Array,  # (n_arena,) GLOBAL feature ids
    split_bin: jax.Array,
    default_left: jax.Array,
    missing_bin: int,
    f_local: int,
    feature_axis: str,
) -> jax.Array:
    """RepartitionInstances when the winning feature's bins may live on
    another feature shard: the owner computes the route (1=left, 2=right)
    and a psum broadcasts it to all shards (n_rows int32 per level)."""
    my = jax.lax.axis_index(feature_axis)
    pos = jnp.maximum(positions, 0)
    active = positions >= 0
    splits_here = split_mask[pos] & active

    f_glob = feature[pos]
    owned = (f_glob // f_local) == my
    f_loc = jnp.clip(f_glob - my * f_local, 0, f_local - 1)
    b = jnp.take_along_axis(bins, f_loc[:, None], axis=1)[:, 0]
    go_left = jnp.where(b == missing_bin, default_left[pos], b <= split_bin[pos])
    route = jnp.where(splits_here & owned, jnp.where(go_left, 1, 2), 0)
    # int8 on the wire: exactly one shard contributes a nonzero (<=2) value,
    # so the psum fits in int8 — 4x fewer routing bytes per level (§Perf
    # GBDT iteration 2; routing dominates collectives for narrow matrices).
    route = jax.lax.psum(route.astype(jnp.int8), feature_axis).astype(jnp.int32)
    child = 2 * pos + route
    return jnp.where(splits_here, child, -1).astype(jnp.int32)
