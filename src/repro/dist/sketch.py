"""Device-sharded quantile sketch construction (paper §quantiles).

The paper moves quantile sketch construction onto the accelerator because it
is a considerable preprocessing cost, and distributes it data-parallel: each
device summarises its row shard, then the summaries are merged. This module
reproduces that split on top of the mergeable `StreamingQuantileSketch`
(DESIGN.md §11):

  * **Device phase** — the O(n log n) part. Under `shard_map`, every shard
    fills NaN -> +inf and sorts each of its columns on device (one fused XLA
    program across all shards), also counting finite entries. No
    inter-device communication happens here.
  * **Host phase** — each shard's presorted columns become exact summaries
    via `StreamingQuantileSketch.push_sorted` (no host re-sort), and the
    per-shard sketches combine by a **log-depth pairwise tree merge**.
    Merging exact summaries is exact and associative, so with adequate
    capacity the merged cuts match single-shot `compute_cuts`; under
    pruning, tree merging performs O(log S) prune rounds instead of the
    sequential fold's O(S), tightening the rank-error bound.

`sharded_sketch_cuts` is the one-call front door used by
`DeviceDMatrix(cuts=...)` precomputation and `ExternalDMatrix(sketch_shards=)`.
"""
from __future__ import annotations

from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.quantile import (
    DEFAULT_MAX_BINS,
    StreamingQuantileSketch,
)

from jax.sharding import PartitionSpec as P


def tree_merge(sketches: Sequence[StreamingQuantileSketch]):
    """Merge sketches pairwise in log-depth order.

    Round t merges sketch 2k with sketch 2k+1; after ceil(log2(S)) rounds
    one sketch remains. Exact summaries make the result merge-order
    invariant; pruned summaries see at most ceil(log2(S)) prune rounds on
    any leaf-to-root path (vs S-1 for a sequential fold).

    Mutates the sketches (merge folds right into left); the survivor is
    returned.
    """
    sketches = list(sketches)
    if not sketches:
        raise ValueError("tree_merge needs at least one sketch")
    while len(sketches) > 1:
        nxt = []
        for i in range(0, len(sketches) - 1, 2):
            nxt.append(sketches[i].merge(sketches[i + 1]))
        if len(sketches) % 2:
            nxt.append(sketches[-1])
        sketches = nxt
    return sketches[0]


def _device_sort_phase(x, mesh, data_axes):
    """Sort every column per shard on device; return host arrays.

    Returns (sorted_cols, n_valid): sorted_cols is (n_shards, shard_rows,
    n_features) with each column ascending, NaN pushed to the tail as +inf;
    n_valid is (n_shards, n_features) finite counts.
    """
    axes = tuple(data_axes)
    n_shards = int(np.prod([mesh.shape[a] for a in axes]))
    n = x.shape[0]
    if n % n_shards:
        raise ValueError(
            f"rows ({n}) must divide evenly across {n_shards} shards for "
            f"the device sketch phase"
        )

    def shard_fn(xs):
        finite = jnp.isfinite(xs)
        filled = jnp.where(finite, xs, jnp.inf)
        srt = jnp.sort(filled, axis=0)
        nv = jnp.sum(finite, axis=0, dtype=jnp.int32)[None, :]
        return srt, nv

    srt, nv = jax.shard_map(
        shard_fn,
        mesh=mesh,
        in_specs=(P(axes, None),),
        out_specs=(P(axes, None), P(axes, None)),
        check_vma=False,
    )(jnp.asarray(x, jnp.float32))
    srt_h = np.asarray(jax.device_get(srt)).reshape(n_shards, n // n_shards,
                                                    x.shape[1])
    nv_h = np.asarray(jax.device_get(nv)).reshape(n_shards, x.shape[1])
    return srt_h, nv_h


def sharded_sketch_cuts(
    x,
    *,
    max_bins: int = DEFAULT_MAX_BINS,
    capacity: int = 1024,
    mesh: jax.sharding.Mesh | None = None,
    data_axes: Sequence[str] = ("data",),
    n_shards: int | None = None,
) -> jax.Array:
    """Quantile cuts via per-shard sketches + log-depth tree merge.

    With `mesh`, the sort runs sharded on device (`shard_map`) and the
    number of shards is the mesh's data-axis extent. Without a mesh,
    `n_shards` (default 1) row-splits on host — the same merge tree, useful
    for tests and for bounding host working memory.

    Returns cuts shaped exactly like `compute_cuts(x, max_bins)`.
    """
    x = np.asarray(x, np.float32) if not isinstance(x, jax.Array) else x
    n, f = x.shape
    if mesh is not None:
        srt, nv = _device_sort_phase(x, mesh, data_axes)
        shards = srt.shape[0]
        sketches = []
        for s in range(shards):
            sk = StreamingQuantileSketch(f, max_bins, capacity)
            sk.push_sorted(srt[s], nv[s])
            sketches.append(sk)
        return tree_merge(sketches).get_cuts()
    shards = max(1, int(n_shards or 1))
    xh = np.asarray(x, np.float32)
    bounds = np.linspace(0, n, shards + 1, dtype=np.int64)
    sketches = []
    for s in range(shards):
        sk = StreamingQuantileSketch(f, max_bins, capacity)
        part = xh[bounds[s]: bounds[s + 1]]
        if part.shape[0]:
            sk.push(part)
        sketches.append(sk)
    return tree_merge(sketches).get_cuts()
