"""Multi-device GBDT training (paper §2.3, Algorithm 1) via shard_map.

Rows are partitioned across the `data` (and `pod`) mesh axes — the paper's
"each GPU processes a subset of training instances". Each shard builds
partial histograms; a pluggable `Collective` strategy combines them (the
NCCL AllReduceHistograms call — psum, explicit ring, or hierarchical
two-level, optionally with compressed bin sums; see dist.collective). Split
evaluation and tree state are replicated, positions stay shard-local. The
per-round function is a single shard_map body, so XLA sees one SPMD program
with exactly one all-reduce per tree level.

This module supersedes `repro.core.distributed` (which re-exports it for
back compatibility). All round inputs travel as one named `RoundInputs`
pytree so every strategy shares a single shard_map signature.

Beyond-paper option (`feature_shards` > 1): histograms are additionally
sharded over features on the `model` axis, turning the full-histogram
all-reduce into a reduce-scatter-shaped psum of 1/p of the bytes, with each
shard evaluating only its features and an argmax-allgather of the (tiny)
per-node best-split records. See EXPERIMENTS.md §Perf.
"""
from __future__ import annotations

from typing import Any, NamedTuple, Sequence

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core import compress as C
from repro.core import objectives as O
from repro.core import resilience as RES
from repro.core import sampling as SMP
from repro.core import tree as T
from repro.dist.collective import (
    Collective,
    get_collective,
    round_comm_stats,
)


class RoundInputs(NamedTuple):
    """Everything one distributed boosting round consumes, as ONE pytree.

    One named structure replaces the old positional 4-or-5 argument
    signature (the replicated stochastic key used to ride along as an
    ad-hoc 5th shard_map arg): `specs()` builds the matching shard_map
    in_specs, so every dist/ strategy shares a single signature and adding
    a replicated field is a one-line change. `rkey=None` is an empty
    pytree leaf — the same compiled signature serves deterministic fits.
    """

    data: Any  # row-sharded matrix (dense | packed words | chunk stack)
    margins: Any  # (n_local, k) row-sharded
    y: Any  # (n_local, ...) row-sharded labels
    cuts: Any  # replicated (f, n_cuts)
    rkey: Any = None  # replicated per-round PRNG key (stochastic fits)

    @staticmethod
    def specs(data_spec, row_spec, stochastic: bool) -> "RoundInputs":
        return RoundInputs(
            data=data_spec,
            margins=row_spec,
            y=row_spec,
            cuts=P(),
            rkey=P() if stochastic else None,
        )


def make_mesh(axis_shapes, axis_names, devices=None) -> jax.sharding.Mesh:
    """`jax.make_mesh` with every axis `Auto`: the sharded round is written
    for shard_map plus compiler-propagated shardings, not explicit-axis
    sharding-in-types (jax.make_mesh's default)."""
    return jax.make_mesh(
        axis_shapes, axis_names,
        axis_types=(jax.sharding.AxisType.Auto,) * len(axis_names),
        devices=devices,
    )


# Compiled per-round shard_map programs and eval-margin updaters, keyed by
# static config (cuts/data are traced arguments) — mirrors
# booster._TRAIN_FN_CACHE so refits with mesh= skip recompilation too.
_ROUND_FN_CACHE: dict = {}
_APPLY_EVAL_CACHE: dict = {}


def make_distributed_round(
    cfg,
    obj: O.Objective,
    mesh: jax.sharding.Mesh,
    data_axes: Sequence[str] = ("data",),
    n_rows_per_shard: int | None = None,
    bits: int | None = None,
    chunk_rows: int | None = None,
    collective: Collective | None = None,
):
    """Returns a jit'd per-round function over a RoundInputs pytree.

    The returned fn takes one `RoundInputs` whose data/margins/y are
    row-sharded over data_axes and cuts/rkey replicated; tree output is
    replicated. Cached by static config (incl. the collective's identity
    key) so repeated fits reuse the compiled program.

    `collective` picks the histogram-reduction strategy (default: exact
    psum — the pre-subsystem program, bit for bit). `chunk_rows` set means
    external-memory data: each shard holds a stack of independently packed
    chunks (its row shard), and the per-level histogram is a chunk-scan
    on-shard followed by the usual allreduce — the chunk loop composes
    with Algorithm 1's AllReduce unchanged.
    """
    if collective is None:
        collective = get_collective("psum", mesh, data_axes)
    # Objective is a hashable NamedTuple; registry lookups return singletons,
    # so registered (incl. custom-registered) objectives key stably.
    key = (cfg, obj, mesh, tuple(data_axes), n_rows_per_shard, bits,
           chunk_rows, collective.key)
    cached = _ROUND_FN_CACHE.get(key)
    if cached is not None:
        return cached
    k = obj.n_outputs(cfg.n_classes)
    cfg_kw = O.config_kwargs(cfg)  # static under shard_map (cfg keys cache)
    chunked = chunk_rows is not None
    stoch = SMP.stochastic_params(cfg)
    sentinel = cfg.numeric_check != "off"
    compressed = collective.compression is not None
    # Static shard geometry for the shared-key sampling (DESIGN.md §12):
    # every shard draws the SAME global row selection / feature masks from
    # the replicated per-round key, then slices its own rows — identical to
    # the single-device sample, no extra collective, allreduce unchanged.
    axis_sizes = tuple(mesh.shape[a] for a in data_axes)
    n_shards = 1
    for s in axis_sizes:
        n_shards *= s

    def _shard_offset(n_local):
        lin = jnp.int32(0)
        for a, s in zip(data_axes, axis_sizes):
            lin = lin * s + jax.lax.axis_index(a)
        return lin * n_local

    def round_body(inputs: RoundInputs):
        from repro.core import booster as B  # lazy: avoid import cycle

        data, margins, y, cuts, rkey = inputs
        collective.begin_round()  # trace-time fallback tally reset
        if chunked:
            # External-memory: this shard's chunk stack is its matrix.
            rep = C.ChunkedPackedBins(
                packed=data, bits=bits, chunk_rows=chunk_rows,
                n_rows=n_rows_per_shard,
            )
        elif cfg.compress_matrix:
            # Packed-native: each shard's words ARE its training matrix —
            # no per-round unpack, no dense (n, f) bins (DESIGN.md §2).
            rep = C.PackedBins(packed=data, bits=bits, n_rows=n_rows_per_shard)
        else:
            rep = data
        n_features = (
            rep.n_features if cfg.compress_matrix or chunked
            else rep.shape[1]
        )
        with jax.named_scope("gradient"):
            gh_all = obj.grad(margins, y, **cfg_kw)
            gh_raw = gh_all
            if cfg.numeric_check == "clamp":
                gh_all = RES.clamp_gradients(gh_all)
        trees = []
        for c in range(k):
            with jax.named_scope("gradient"):  # the class's (n, 2) view
                gh_c = gh_all[:, c, :]
            ctx = None
            if stoch is not None:
                n_local = margins.shape[0]
                ctx, gh_c = SMP.make_tree_context(
                    stoch, jax.random.fold_in(rkey, c), gh_c, n_features,
                    compact=False,
                    n_total=n_local * n_shards,
                    row_offset=_shard_offset(n_local),
                    # GOSS needs the GLOBAL |g| vector: gh is all_gather'd
                    # over the data axes (gather order == the runner's row
                    # linearisation) so every shard draws the identical
                    # replicated selection, then slices at row_offset.
                    axis_name=tuple(data_axes),
                )
            tr = T.grow_tree(
                rep,
                gh_c,
                cuts,
                cfg.max_depth,
                cfg.max_bins,
                cfg.split_params,
                growth=cfg.growth,
                max_leaves=cfg.max_leaves or 2**cfg.max_depth,
                ctx=ctx,
                collective=collective,
            )
            # Materialise tree arrays before the margin update (same
            # barrier as booster._round_step_fn — see DESIGN.md §11).
            trees.append(jax.lax.optimization_barrier(tr))
        stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *trees)
        # One barriered add for all k columns, shared with the
        # single-device scan so both compile the update identically.
        new_margins = B._apply_stacked_trees(cfg, stacked, rep, margins)
        out = [stacked, new_margins]
        if sentinel:
            # Gradients/margins are shard-local; a shard seeing non-finite
            # values must poison the round globally (trees are replicated),
            # so the bad count is all-reduced before the policy applies.
            ok_local = RES.finite_flags(gh_raw, stacked.leaf_value,
                                        new_margins)
            bad = collective.allreduce(
                jnp.where(ok_local, 0, 1).astype(jnp.int32)
            )
            ok = bad == 0
            if cfg.numeric_check == "warn_skip":
                # Same neutralisation as booster._round_step_fn: zero
                # leaves, -inf gains, round-start margins carried forward.
                stacked = stacked._replace(
                    leaf_value=jnp.where(ok, stacked.leaf_value,
                                         jnp.zeros_like(stacked.leaf_value)),
                    gain=jnp.where(ok, stacked.gain,
                                   jnp.full_like(stacked.gain, -jnp.inf)),
                )
                new_margins = jnp.where(ok, new_margins, margins)
            out = [stacked, new_margins, ok]
        if compressed:
            # Replicated count of hist allreduces that fell back to exact
            # f32 this round (tolerance exceeded) — surfaced in comm_stats.
            out.append(collective.fallback_count())
        return tuple(out)

    axes = tuple(data_axes) if len(data_axes) > 1 else data_axes[0]
    row_spec = P(axes)
    if chunked:
        # chunk stack is (C, F, W): rows live in whole chunks on axis 0.
        data_spec = P(axes, None, None)
    elif cfg.compress_matrix:
        # packed matrix is (F, W): rows live in the words axis.
        data_spec = P(None, axes)
    else:
        data_spec = P(axes, None)

    in_specs = (RoundInputs.specs(data_spec, row_spec, stoch is not None),)
    out_specs = (P(), row_spec)
    if sentinel:
        out_specs = out_specs + (P(),)  # all-reduced ok flag, replicated
    if compressed:
        out_specs = out_specs + (P(),)  # fallback tally, replicated
    shard_fn = jax.shard_map(
        round_body,
        mesh=mesh,
        in_specs=in_specs,
        out_specs=out_specs,
        check_vma=False,
    )
    fn = _ROUND_FN_CACHE[key] = jax.jit(shard_fn)
    return fn


def make_chunk_runner(
    cfg,
    obj: O.Objective,
    dmat,
    mesh: jax.sharding.Mesh,
    data_axes: Sequence[str],
    eval_pbs: tuple = (),
    eval_ys: tuple = (),
    eval_extras: tuple = (),
    metrics: tuple = (),
    track_metric: bool = False,
    collective="psum",
    compression: str | None = None,
    comm_tolerance: float = 0.05,
):
    """The multi-device strategy behind Booster.fit(dtrain, mesh=...).

    Shards the DeviceDMatrix's rows over the data axes (re-packing the words
    per shard so each shard decodes independently), then exposes the same
    chunk interface as the single-device scan:

        run(length, start_round, margins, eval_margins) ->
            (margins, stacked_trees (length, k, arena...),
             train_metrics tuple-per-metric of (length,), eval_margins,
             eval_metrics tuple-per-set of tuple-per-metric of (length,),
             sentinel flags ((length,) bool, or () when numeric_check="off"))

    plus two attributes the Booster surfaces: `run.comm_stats` (analytic
    per-round CommStats for the chosen collective/compression) and
    `run.fallback_events` (measured count of compressed allreduces that
    fell back to exact f32, accumulated across calls).

    The per-round loop dispatches one shard_map'd program per round (one
    allreduce per tree level, Algorithm 1); eval-set margins are maintained
    incrementally on replicated eval data, and every requested metric is
    evaluated per round with values staying on device until the Booster
    reads them at chunk granularity — the same multi-metric stack as the
    single-device scan.
    """
    from repro.core.dmatrix import ExternalDMatrix

    coll = get_collective(collective, mesh, data_axes,
                          compression=compression, tolerance=comm_tolerance)
    n = dmat.n_rows
    n_shards = 1
    for a in data_axes:
        n_shards *= mesh.shape[a]
    if n % n_shards != 0:
        raise ValueError(
            f"n_rows={n} must be divisible by the {n_shards} data shards "
            "(truncate or pad upstream)"
        )
    cuts = dmat.cuts
    if isinstance(dmat, ExternalDMatrix):
        # External-memory + multi-device: whole chunks are the sharding
        # unit (each chunk already decodes independently, so no per-shard
        # re-packing is needed). Shard boundaries must align with chunk
        # boundaries so each shard's rows are exactly its chunks' rows.
        if n % dmat.chunk_rows != 0:
            raise ValueError(
                f"external-memory training with mesh= requires n_rows={n} "
                f"to be a multiple of chunk_rows={dmat.chunk_rows} (the "
                "last chunk must be full so shards get whole chunks)"
            )
        if dmat.n_chunks % n_shards != 0:
            raise ValueError(
                f"n_chunks={dmat.n_chunks} must be divisible by the "
                f"{n_shards} data shards; pick chunk_rows so chunks "
                "distribute evenly"
            )
        bits, n_per = dmat.bits, n // n_shards
        data = dmat.packed_bins().packed
        chunk_rows = dmat.chunk_rows
    elif cfg.compress_matrix:
        # Re-packed per shard and placed by the DeviceDMatrix (cached there).
        bits = dmat.bits
        n_per = n // n_shards
        chunk_rows = None
        data = None
    else:
        data = dmat.matrix.unpack()
        bits, n_per, chunk_rows = None, None, None

    axes = tuple(data_axes) if len(data_axes) > 1 else data_axes[0]
    row_sharding = jax.NamedSharding(mesh, P(axes))
    if chunk_rows is not None:
        data_spec = P(axes, None, None)  # whole chunks per shard
    elif cfg.compress_matrix:
        data_spec = P(None, axes)
    else:
        data_spec = P(axes, None)
    data_sharding = jax.NamedSharding(mesh, data_spec)
    y = jax.device_put(dmat.label, row_sharding)
    if data is None:
        data = dmat.sharded_packed(n_shards, data_sharding)
    else:
        data = jax.device_put(data, data_sharding)
    round_fn = make_distributed_round(
        cfg, obj, mesh, data_axes, n_rows_per_shard=n_per, bits=bits,
        chunk_rows=chunk_rows, collective=coll,
    )

    from repro.core import booster as B  # lazy: avoid import cycle

    apply_eval = _APPLY_EVAL_CACHE.get(cfg)
    if apply_eval is None:
        apply_eval = _APPLY_EVAL_CACHE[cfg] = jax.jit(
            lambda stacked, pb, m, _cfg=cfg:
                B._apply_stacked_trees(_cfg, stacked, pb, m)
        )

    train_kw = O.config_kwargs(cfg)  # group_ids is single-device only
    stoch = SMP.stochastic_params(cfg)
    base_key = jax.random.PRNGKey(cfg.seed) if stoch is not None else None

    sentinel = cfg.numeric_check != "off"
    compressed = coll.compression is not None

    def run(length, start_round, margins, eval_margins):
        margins = jax.device_put(margins, row_sharding)
        trees, tr_rows, ev_rows, ok_rows, fb_rows = [], [], [], [], []
        for r in range(length):
            if stoch is None:
                rkey = None
            else:
                # Same fold path as the single-device scan body, from the
                # ABSOLUTE round index — single- and multi-device fits draw
                # identical samples/masks (DESIGN.md §12).
                rkey = jax.random.fold_in(
                    base_key, jnp.asarray(start_round + r, jnp.int32)
                )
            out = list(round_fn(RoundInputs(data, margins, y, cuts, rkey)))
            if compressed:
                fb_rows.append(out.pop())
            if sentinel:
                stacked, margins, ok = out
                ok_rows.append(ok)
            else:
                stacked, margins = out
            trees.append(stacked)
            eval_margins = tuple(
                apply_eval(stacked, pb, em)
                for pb, em in zip(eval_pbs, eval_margins)
            )
            if track_metric:
                tr_rows.append(tuple(
                    m.fn(margins, y, **train_kw).astype(jnp.float32)
                    for m in metrics
                ))
            ev_rows.append(tuple(
                tuple(m.fn(em, ey, **ex).astype(jnp.float32) for m in metrics)
                for em, ey, ex in zip(eval_margins, eval_ys, eval_extras)
            ))
        all_trees = jax.tree.map(lambda *xs: jnp.stack(xs), *trees)
        tr_metrics = tuple(
            jnp.stack([row[j] for row in tr_rows])
            for j in range(len(metrics))
        ) if track_metric else ()
        ev_metrics = tuple(
            tuple(jnp.stack([row[i][j] for row in ev_rows])
                  for j in range(len(metrics)))
            for i in range(len(eval_pbs))
        )
        flags = jnp.stack(ok_rows) if sentinel else ()
        if fb_rows:
            run.fallback_events += int(sum(int(f) for f in fb_rows))
        return margins, all_trees, tr_metrics, eval_margins, ev_metrics, flags

    run.fallback_events = 0
    run.comm_stats = round_comm_stats(
        coll,
        max_depth=cfg.max_depth,
        n_features=int(cuts.shape[0]),
        max_bins=cfg.max_bins,
        n_trees_per_round=obj.n_outputs(cfg.n_classes),
        sentinel=sentinel,
    )
    return run


def train_distributed(
    x,
    y,
    cfg,
    mesh: jax.sharding.Mesh,
    data_axes: Sequence[str] = ("data",),
    verbose_every: int = 0,
):
    """Deprecated shim: quantises x and runs Booster.fit(dtrain, mesh=mesh).

    Returns the same Booster object as single-device training (the old
    (ensemble, margins, history) tuple is reachable as attributes)."""
    from repro.core.booster import Booster
    from repro.core.dmatrix import DeviceDMatrix

    dtrain = DeviceDMatrix(x, label=y, max_bins=cfg.max_bins)
    return Booster(cfg).fit(dtrain, verbose_every=verbose_every, mesh=mesh,
                            data_axes=data_axes)
