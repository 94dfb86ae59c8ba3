"""Gradient boosting — the Figure 1 pipeline behind a two-noun public API.

The API is organised around XGBoost's two nouns (Chen & Guestrin 2016):

  * `DeviceDMatrix` (dmatrix.py) — quantise + compress ONCE, reuse forever.
  * `Booster` — the single entry point for `fit(dtrain, evals=[...])`,
    `update(dtrain, n_rounds)` (warm-start continued training),
    `predict(x | DeviceDMatrix)`, `eval(dmat)`, `save`/`load`.

The model is self-describing: a `Booster` checkpoint carries its config,
cut points, base score and best_iteration, so `Booster.load(path).predict(x)`
needs no caller-supplied `max_depth` / `objective` / `n_classes`.

Training is ONE compiled program: a jax.lax.scan over boosting rounds whose
ys-stack is the preallocated (n_rounds * k, arena) ensemble arena. Per round
(all phases on-accelerator, as in the paper): predict (incremental margins)
-> gradient evaluation -> quantised-histogram tree construction -> margin
update. Evaluation sets ride INSIDE the scan: each eval set is a
`DeviceDMatrix` quantised with the training cuts, its margins are maintained
incrementally next to the training margins, and EVERY requested eval metric
(`fit(eval_metric=[...], custom_metric=...)`) comes out as a scan ys-stack
entry — no per-round host round trips. With `early_stopping_rounds=e` the
scan runs in compiled chunks of e rounds with one host-side check per chunk
(overtraining bounded by < 2e rounds), stopping on the LAST metric of the
LAST eval set in that metric's declared direction, and the stored ensemble
is truncated to `best_iteration + 1` rounds.

Objectives and metrics are pluggable registries (DESIGN.md §10):
`fit(obj=...)` traces custom `(margins, y) -> (g, h)` callables straight
into the scan, and the compiled-fn cache is keyed by the resolved
Objective/Metric objects, so repeat fits with the same plugins reuse the
compiled program.

Feature quantisation + compression happen once, at DeviceDMatrix
construction (Figure 1's left boxes). With compress_matrix=True the
bit-packed words are the *only* training-set representation (paper §2.2,
DESIGN.md §2): histograms are built from the packed words, row
repartitioning and training-set prediction extract the needed feature column
from the words on the fly. The dense (n, f) int32 bins array is never
materialised again after quantisation.

Multiclass trains n_classes trees per round on softmax gradients (round-robin
class layout, XGBoost's scheme). The multi-device path (distributed.py) is a
strategy behind the same `Booster.fit(dtrain, mesh=...)` signature and
returns the identical object.

The old `train()` / `predict()` functions survive as thin deprecated shims
over this API.
"""
from __future__ import annotations

import dataclasses
import functools
import warnings
from dataclasses import dataclass
from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core import compress as C
from repro.core import metrics as M
from repro.core import objectives as O
from repro.core import quantile as Q
from repro.core import resilience as RES
from repro.core import sampling as SMP
from repro.core import split as S
from repro.core import tree as T
from repro.core import predict as PR
from repro.core.dmatrix import DeviceDMatrix, ExternalDMatrix, cuts_equal
from repro.testing import faults as FA


@dataclass(frozen=True)
class BoosterConfig:
    n_rounds: int = 100
    learning_rate: float = 0.3
    max_depth: int = 6
    max_bins: int = Q.DEFAULT_MAX_BINS
    reg_lambda: float = 1.0
    gamma: float = 0.0
    min_child_weight: float = 1.0
    objective: str = "reg:squarederror"
    n_classes: int = 1
    quantile_alpha: float = 0.5  # reg:quantile pinball target
    growth: str = "depthwise"  # or "lossguide"
    max_leaves: int = 0  # lossguide budget (0 = 2^max_depth)
    use_kernel_histograms: bool = False  # route through the Pallas kernel path
    compress_matrix: bool = True  # paper §2.2 (False = raw int32 bins)
    hist_block_rows: int = 65536  # packed-histogram fallback dense-tile bound
    # Stochastic regularisers + constraints (DESIGN.md §12). All-default
    # values select the exact deterministic pre-stochastic program.
    subsample: float = 1.0  # per-tree row fraction (static round(n*s) buffer)
    colsample_bytree: float = 1.0  # per-tree feature fraction
    colsample_bylevel: float = 1.0  # per-level fraction OF the tree's set
    colsample_bynode: float = 1.0  # per-node fraction OF the level's set
    monotone_constraints: tuple | None = None  # per-feature {-1, 0, +1}
    # GOSS (DESIGN.md §17): sampling_method="goss" keeps the top_rate
    # fraction of rows by |gradient| and uniformly samples other_rate of
    # the rest per tree, reweighting the sampled remainder by
    # (1 - top_rate) / other_rate. Mutually exclusive with subsample < 1.
    sampling_method: str = "uniform"  # or "goss"
    top_rate: float = 0.2  # GOSS: kept fraction of largest-|g| rows
    other_rate: float = 0.1  # GOSS: uniformly sampled fraction of the rest
    seed: int = 0  # PRNG seed; keys fold as (seed, round, class, site)
    # Numeric sentinel (DESIGN.md §13): "off" keeps the exact pre-sentinel
    # compiled program; otherwise a per-round finite flag on grads/hessians/
    # leaf weights rides the ys-stack and the host applies the policy at
    # chunk granularity — "raise" (NumericError), "warn_skip" (zero the
    # offending trees so later margins stay clean), "clamp" (nan_to_num +
    # clip gradients before tree growth).
    numeric_check: str = "off"

    def __post_init__(self):
        RES.validate_numeric_policy(self.numeric_check)
        mc = self.monotone_constraints
        if mc is not None:
            mc = tuple(int(c) for c in mc)  # hashable (lists/arrays coerce)
            object.__setattr__(self, "monotone_constraints", mc)
            if any(c not in (-1, 0, 1) for c in mc):
                raise ValueError(
                    f"monotone_constraints must be -1/0/+1, got {mc}"
                )
        for knob in ("subsample", "colsample_bytree", "colsample_bylevel",
                     "colsample_bynode"):
            v = getattr(self, knob)
            if not 0.0 < v <= 1.0:
                raise ValueError(f"{knob} must be in (0, 1], got {v}")
        if self.sampling_method not in ("uniform", "goss"):
            raise ValueError(
                f"sampling_method must be 'uniform' or 'goss', "
                f"got {self.sampling_method!r}"
            )
        if self.sampling_method == "goss":
            for knob in ("top_rate", "other_rate"):
                v = getattr(self, knob)
                if not 0.0 < v < 1.0:
                    raise ValueError(
                        f"{knob} must be in (0, 1) with sampling_method="
                        f"'goss', got {v}"
                    )
            if self.top_rate + self.other_rate > 1.0:
                raise ValueError(
                    f"top_rate + other_rate must be <= 1.0, got "
                    f"{self.top_rate} + {self.other_rate}"
                )
            if self.subsample < 1.0:
                raise ValueError(
                    "sampling_method='goss' replaces uniform row "
                    "subsampling — leave subsample at 1.0"
                )

    @property
    def split_params(self) -> S.SplitParams:
        return S.SplitParams(self.reg_lambda, self.gamma, self.min_child_weight)


def _tree_margin_delta(cfg: BoosterConfig, tr: T.Tree, data) -> jax.Array:
    """One tree's margin contribution (learning rate already applied) over
    all rows, straight from the quantised representation (packed, chunked
    or dense) — no Ensemble construction."""
    mb = cfg.max_bins - 1
    if getattr(data, "is_streamed", False):
        # Streaming executor (core/stream.py): per-chunk traversal over the
        # host-resident stack, same jitted kernel as the chunked scan body.
        delta = data.traverse_tree(tr, mb, cfg.max_depth)
    elif isinstance(data, C.ChunkedPackedBins):
        delta = PR.traverse_tree_chunked(
            tr.feature, tr.split_bin, tr.default_left, tr.leaf_value, tr.is_leaf,
            data.packed, data.bits, data.chunk_rows, data.n_rows, mb,
            cfg.max_depth,
        )
    elif isinstance(data, C.PackedBins):
        delta = PR.traverse_tree_packed(
            tr.feature, tr.split_bin, tr.default_left, tr.leaf_value, tr.is_leaf,
            data.packed, data.bits, data.n_rows, mb, cfg.max_depth,
        )
    else:
        delta = PR.traverse_tree_binned(
            tr.feature, tr.split_bin, tr.default_left, tr.leaf_value, tr.is_leaf,
            data, mb, cfg.max_depth,
        )
    return cfg.learning_rate * delta


def _apply_stacked_trees(cfg: BoosterConfig, stacked: T.Tree, data,
                         margins: jax.Array) -> jax.Array:
    """Add one round's k stacked trees (unscaled leaves, leading axis k) to
    margins — the training-set margin update of the round step, eval-set
    margins inside the scan, and the distributed per-round loop all route
    through here.

    The update is ONE full-array add of an optimization_barrier'd update
    stack (each margin column receives exactly one tree's contribution, so
    this is elementwise-identical to per-class updates). The barrier is
    load-bearing for external memory: without it XLA may contract
    `margins + lr * delta` into an FMA — or rematerialise tree arithmetic
    inside the fused update — differently depending on the data
    representation's producer graph, silently breaking the bit-identity
    between the in-memory and chunked paths (DESIGN.md §11)."""
    with jax.named_scope("margins"):
        k = stacked.feature.shape[0]
        if getattr(data, "is_streamed", False):
            # Streamed executor: the traversals run eagerly per chunk, but the
            # scale-and-add must compile as ONE jitted program. XLA's CPU
            # emitter contracts `margins + lr * delta` into a single-rounding
            # FMA inside compiled programs — optimization_barrier does not
            # block the instruction-level contraction — while eager op-by-op
            # dispatch rounds the multiply and the add separately. Compiling
            # the same mul/barrier/add subgraph standalone reproduces the
            # scan body's rounding exactly (the bit-identity tests pin this).
            mb = cfg.max_bins - 1
            deltas = jnp.stack(
                [
                    data.traverse_tree(jax.tree.map(lambda a: a[c], stacked),
                                       mb, cfg.max_depth)
                    for c in range(k)
                ],
                axis=1,
            )
            return _streamed_margin_update(margins, deltas, cfg.learning_rate)
        updates = jnp.stack(
            [
                _tree_margin_delta(cfg, jax.tree.map(lambda a: a[c], stacked), data)
                for c in range(k)
            ],
            axis=1,
        )
        return margins + jax.lax.optimization_barrier(updates)


@functools.partial(jax.jit, static_argnames=("lr",))
def _streamed_margin_update(margins: jax.Array, deltas: jax.Array,
                            lr: float) -> jax.Array:
    """The margin update's arithmetic tail (scale, barrier, add) compiled
    standalone — the streamed twin of the in-scan update (see the streamed
    branch of _apply_stacked_trees for why this must be jitted)."""
    return margins + jax.lax.optimization_barrier(jnp.float32(lr) * deltas)


def _round_step_fn(cfg: BoosterConfig, obj: O.Objective, hist_builder=None):
    """One boosting round: gradients -> K trees -> margins. Pure (not jit'd
    on its own) so it can be the body of the training scan. `cuts` is an
    argument, not a closure, so compiled train functions can be cached by
    static config alone and reused across DeviceDMatrices.

    With stochastic knobs active (DESIGN.md §12) the per-round PRNG key
    `rkey` (folded from (seed, round) by the scan body) is folded per class
    tree and drives row/column sampling INSIDE the compiled program; the
    per-tree row buffer is compacted statically so a subsampled round does
    proportionally less scatter work. Kernel hist builders aren't
    row-subset aware, so they fall back to masked-mode subsampling.

    With cfg.numeric_check != "off" the step returns a third element: a
    scalar bool `ok` (all grads/hessians/leaf values/margins finite this
    round) that rides the scan's ys-stack for host-side policy handling.
    The default config keeps the exact two-tuple return and traced program.
    The nan_grad fault site (repro.testing.faults) is read at trace time —
    callers that cache compiled programs key on faults.trace_key."""
    k = obj.n_outputs(cfg.n_classes)
    stoch = SMP.stochastic_params(cfg)
    compact_rows = hist_builder is None
    sentinel = cfg.numeric_check != "off"
    fault = FA.active("nan_grad")

    def round_step(data, margins, y, extra, cuts, rkey=None, round_idx=None):
        if stoch is not None and rkey is None:
            raise ValueError(
                "this config has stochastic knobs (subsample/colsample/"
                "monotone or non-default seed use) — the round step needs "
                "a per-round PRNG key (rkey)"
            )
        with jax.named_scope("gradient"):
            gh_all = obj.grad(margins, y, **extra)  # (n, k, 2)
            if fault is not None and round_idx is not None:
                bad_round = int(fault.payload.get("round", 0))
                bad_val = float(fault.payload.get("value", np.nan))
                gh_all = jnp.where(jnp.equal(round_idx, bad_round),
                                   jnp.full_like(gh_all, bad_val), gh_all)
            gh_raw = gh_all
            if cfg.numeric_check == "clamp":
                gh_all = RES.clamp_gradients(gh_all)
        n_features = getattr(data, "n_features", None)
        if n_features is None:  # dense (n, f) bins array
            n_features = data.shape[1]
        trees = []
        for c in range(k):
            with jax.named_scope("gradient"):  # the class's (n, 2) view
                gh_c = gh_all[:, c, :]
            ctx = None
            if stoch is not None:
                ctx, gh_c = SMP.make_tree_context(
                    stoch, jax.random.fold_in(rkey, c), gh_c, n_features,
                    compact=compact_rows,
                )
            tr = T.grow_tree(
                data,
                gh_c,
                cuts,
                cfg.max_depth,
                cfg.max_bins,
                cfg.split_params,
                growth=cfg.growth,
                max_leaves=cfg.max_leaves or 2**cfg.max_depth,
                hist_builder=hist_builder,
                hist_block_rows=cfg.hist_block_rows,
                ctx=ctx,
            )
            # Materialise the tree arrays before they fan out to the margin
            # update: without the barrier XLA may rematerialise leaf-value
            # arithmetic inside the fused traversal, with representation-
            # dependent FMA contraction (DESIGN.md §11).
            trees.append(jax.lax.optimization_barrier(tr))
        stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *trees)
        # Trees only depend on round-start gradients, so the k margin
        # columns update in one barriered add (see _apply_stacked_trees).
        new_margins = _apply_stacked_trees(cfg, stacked, data, margins)
        if not sentinel:
            return stacked, new_margins
        ok = RES.finite_flags(gh_raw, stacked.leaf_value, new_margins)
        if cfg.numeric_check == "warn_skip":
            # Neutralise the offending trees: zero leaves (the tree adds
            # nothing to any margin), -inf gains (importances ignore it),
            # and carry the round-start margins forward unpolluted.
            stacked = stacked._replace(
                leaf_value=jnp.where(ok, stacked.leaf_value,
                                     jnp.zeros_like(stacked.leaf_value)),
                gain=jnp.where(ok, stacked.gain,
                               jnp.full_like(stacked.gain, -jnp.inf)),
            )
            new_margins = jnp.where(ok, new_margins, margins)
        return stacked, new_margins, ok

    return round_step


def _make_round_step(cfg: BoosterConfig, obj: O.Objective, cuts: jax.Array,
                     hist_builder=None):
    """Round step with `cuts` bound (the jaxpr-discipline tests and phase
    benchmarks inspect this closed form). Stochastic configs must pass the
    per-round key: `round_step(data, margins, y, extra, rkey=...)`."""
    step = _round_step_fn(cfg, obj, hist_builder)

    def round_step(data, margins, y, extra, rkey=None, round_idx=None):
        return step(data, margins, y, extra, cuts, rkey, round_idx)

    return round_step


# Compiled train functions, keyed by static config + objective + metric
# tuple (cuts/data are traced arguments). Objective and Metric are hashable
# NamedTuples and registry lookups return singletons — a refit with the same
# config, same (possibly custom) objective and same eval metrics reuses the
# compiled program as long as shapes match, so the quantise-once API isn't
# eaten by per-fit recompilation (DESIGN.md §10).
_TRAIN_FN_CACHE: dict = {}


def _make_train_fn(cfg: BoosterConfig, obj: O.Objective, cuts: jax.Array,
                   hist_builder, metrics: tuple, track_metric: bool,
                   n_rounds: int | None = None):
    """The whole training run as one jit: scan over rounds.

    Returns a function
      (data, margins0, y, extra, eval_data, eval_margins0, eval_y,
       eval_extra) ->
      (final_margins, stacked_trees (n_rounds, k, arena...),
       train_metrics tuple-per-metric of (n_rounds,), final_eval_margins,
       eval_metrics tuple-per-set of tuple-per-metric of (n_rounds,))

    With stochastic knobs in cfg the returned function instead takes
      (base_key, start_round, data, margins0, y, extra, ...)
    where start_round is the ABSOLUTE index of the first round — the scan
    folds (base_key, round) per step, so ES chunks and update()
    continuation replay one long fit's key stream (see _run_rounds'
    run_chunk, the only internal caller).

    Eval sets ride inside the scan: eval_data is a tuple of PackedBins
    (quantised with the TRAINING cuts), their margins are carried next to
    the training margins, and EVERY requested metric of every eval set
    lands in its own ys-stack entry — multi-metric per-round history with
    zero host round trips.

    Every variant returns a 6-tuple whose last element is the numeric
    sentinel's per-round flags: a (length,) bool array when
    cfg.numeric_check != "off", else the empty pytree () (no ys entry, so
    the default compiled program is unchanged). An armed nan_grad fault
    (repro.testing.faults) is baked in at trace time and keyed into the
    cache, and forces the start_round-taking signature so the injection
    round is absolute.
    """
    length = cfg.n_rounds if n_rounds is None else n_rounds
    fault_key = FA.trace_key("nan_grad")
    key = (cfg, obj, hist_builder, metrics, track_metric, length, fault_key)
    jitted = _TRAIN_FN_CACHE.get(key)
    stoch = SMP.stochastic_params(cfg)
    sentinel = cfg.numeric_check != "off"
    if jitted is None:
        round_step = _round_step_fn(cfg, obj, hist_builder)

        def _make_body(data, y, extra, eval_data, eval_y, eval_extra, cuts,
                       rkey_of, ridx_of):
            def body(carry, x):
                margins, ev = carry
                out = round_step(data, margins, y, extra, cuts, rkey_of(x),
                                 ridx_of(x))
                if sentinel:
                    stacked, new_margins, ok = out
                else:
                    (stacked, new_margins), ok = out, ()
                new_ev, ev_metrics = [], []
                for pb, em, ey, ex in zip(eval_data, ev, eval_y, eval_extra):
                    em = _apply_stacked_trees(cfg, stacked, pb, em)
                    new_ev.append(em)
                    ev_metrics.append(tuple(
                        m.fn(em, ey, **ex).astype(jnp.float32)
                        for m in metrics
                    ))
                tr_metrics = tuple(
                    m.fn(new_margins, y, **extra).astype(jnp.float32)
                    for m in metrics
                ) if track_metric else ()
                return (new_margins, tuple(new_ev)), (stacked, tr_metrics,
                                                      tuple(ev_metrics), ok)
            return body

        def _scan(body, margins0, eval_margins0, xs):
            # `round` names what the round's phases leave unnamed: the loop
            # itself, the ys-stack writes, the trees' stacking.
            with jax.named_scope("round"):
                (margins, ev), (all_trees, tr_metrics, ev_metrics, flags) = \
                    jax.lax.scan(body, (margins0, tuple(eval_margins0)), xs,
                                 length=length if xs is None else None)
            return margins, all_trees, tr_metrics, ev, ev_metrics, flags

        if stoch is not None:
            # Stochastic variant: the base PRNG key and the ABSOLUTE first
            # round index ride in as traced args; the scan folds
            # (key, round) per step so ES chunking and update() continuation
            # replay the identical key stream as one long fit.
            @jax.jit
            def train_fn(cuts, base_key, start_round, data, margins0, y,
                         extra, eval_data=(), eval_margins0=(), eval_y=(),
                         eval_extra=()):
                body = _make_body(
                    data, y, extra, eval_data, eval_y, eval_extra, cuts,
                    lambda r: jax.random.fold_in(base_key, r), lambda r: r,
                )
                xs = start_round + jnp.arange(length, dtype=jnp.int32)
                return _scan(body, margins0, eval_margins0, xs)
        elif fault_key is not None:
            # Deterministic config with an armed nan_grad fault: the scan
            # still needs absolute round indices so the fault fires at its
            # configured round regardless of chunk boundaries.
            @jax.jit
            def train_fn(cuts, start_round, data, margins0, y, extra,
                         eval_data=(), eval_margins0=(), eval_y=(),
                         eval_extra=()):
                body = _make_body(data, y, extra, eval_data, eval_y,
                                  eval_extra, cuts, lambda _: None,
                                  lambda r: r)
                xs = start_round + jnp.arange(length, dtype=jnp.int32)
                return _scan(body, margins0, eval_margins0, xs)
        else:
            @jax.jit
            def train_fn(cuts, data, margins0, y, extra, eval_data=(),
                         eval_margins0=(), eval_y=(), eval_extra=()):
                body = _make_body(data, y, extra, eval_data, eval_y,
                                  eval_extra, cuts, lambda _: None,
                                  lambda _: None)
                return _scan(body, margins0, eval_margins0, None)

        jitted = _TRAIN_FN_CACHE[key] = train_fn
    return functools.partial(jitted, cuts)


def _scale_leaves(ens: PR.Ensemble, eta: float) -> PR.Ensemble:
    """Bake the learning rate into stored leaf values (margins during
    training already used eta; the stored ensemble must match)."""
    return ens._replace(leaf_value=ens.leaf_value * eta)


def _stack_to_ensemble(all_trees: T.Tree, k: int,
                       base_score: float) -> PR.Ensemble:
    """Reshape a scan ys-stack of trees (rounds, k, arena...) into an
    Ensemble in XGBoost's round-robin (rounds * k, arena) layout."""
    arena = all_trees.feature.shape[-1]
    return PR.Ensemble(
        feature=all_trees.feature.reshape(-1, arena),
        split_bin=all_trees.split_bin.reshape(-1, arena),
        threshold=all_trees.threshold.reshape(-1, arena),
        default_left=all_trees.default_left.reshape(-1, arena),
        leaf_value=all_trees.leaf_value.reshape(-1, arena),
        is_leaf=all_trees.is_leaf.reshape(-1, arena),
        gain=all_trees.gain.reshape(-1, arena),
        n_classes=k,
        base_score=base_score,
    )


class Booster:
    """Self-describing gradient-boosted model (XGBoost's `Booster` noun).

    Construct with a `BoosterConfig` (or keyword overrides), then:

        bst = Booster(n_rounds=100, objective="binary:logistic")
        bst.fit(dtrain, evals=[(dvalid, "valid")], early_stopping_rounds=10)
        p = bst.predict(x_new)          # numpy / jax array / DeviceDMatrix
        bst.save(path); Booster.load(path).predict(x_new)  # no extra args

    Both the objective and the eval metrics are pluggable (DESIGN.md §10):
    `fit(obj=...)` accepts a registry name, an `objectives.register_objective`
    result, or a bare `(margins, y) -> (g, h)` callable traced straight into
    the compiled scan; `fit(eval_metric=[...], custom_metric=...)` evaluates
    any number of metrics per round inside the scan, and early stopping is
    keyed to the LAST metric of the LAST eval set with the direction taken
    from that metric's `maximize` flag (XGBoost's convention).

    After fit: `ensemble` (stacked tree arenas), `history` (per-round eval
    records keyed `{set}_{metric}`), `best_iteration`/`best_score` (when
    early stopping ran), `n_rounds_trained`. `update(dtrain, n)` continues
    training by re-entering the scan with the existing margins.
    """

    def __init__(self, cfg: BoosterConfig | None = None, **params):
        if cfg is None:
            cfg = BoosterConfig(**params)
        elif params:
            cfg = dataclasses.replace(cfg, **params)
        self.cfg = cfg
        self.ensemble: PR.Ensemble | None = None
        self.cuts: jax.Array | None = None
        self.base_score: float = 0.0
        self.history: list[dict] = []
        self.best_iteration: int | None = None
        self.best_score: float | None = None
        self.n_rounds_trained: int = 0
        self._obj: O.Objective | None = None  # fit(obj=...) override
        self._metrics: tuple[M.Metric, ...] | None = None
        self._margins: jax.Array | None = None  # training margins cache
        self._train_dmat: DeviceDMatrix | None = None  # cache key for _margins
        # Resilience record (DESIGN.md §13): rounds whose trees were zeroed
        # under numeric_check="warn_skip", and a log of degradations the
        # runtime absorbed (OOM fallback, failed checkpoint writes, clamps).
        self.skipped_rounds: list[int] = []
        self.resilience_events: list[dict] = []
        # Per-fit communication profile of the latest mesh= fit (DESIGN.md
        # §15): wire bytes/round, collective calls, compression fallbacks.
        self.comm_stats: dict | None = None

    # --- small surface -----------------------------------------------------
    @property
    def obj(self) -> O.Objective:
        if self._obj is not None and self._obj.name == self.cfg.objective:
            return self._obj
        return O.get_objective(self.cfg.objective)

    @property
    def margins(self) -> jax.Array | None:
        """Training margins of the last fit/update (TrainState compat)."""
        return self._margins

    @property
    def matrix(self) -> C.CompressedMatrix | None:
        """Compressed matrix of the last training set (TrainState compat).
        None after external-memory fits (no single flat matrix exists)."""
        return getattr(self._train_dmat, "matrix", None)

    def num_boosted_rounds(self) -> int:
        return self.n_rounds_trained

    def _require_fitted(self):
        if self.ensemble is None:
            raise RuntimeError("Booster is not fitted yet — call fit() first")

    # --- training ----------------------------------------------------------
    def _resolve_metrics(self, eval_metric, custom_metric
                         ) -> tuple[M.Metric, ...]:
        """eval_metric: one spec or a sequence of specs (registry names,
        Metric objects, callables, (name, fn[, maximize]) tuples);
        custom_metric: a single extra spec appended LAST, so with early
        stopping it drives the stop (XGBoost's custom_metric semantics).
        Defaults to the objective's metric."""
        metrics = M.resolve_metrics(eval_metric)
        if custom_metric is not None:
            metrics = metrics + (M.get_metric(custom_metric),)
        if not metrics:
            metrics = (M.get_metric(self.obj.default_metric),)
        return metrics

    def _dataset_extra(self, dmat: DeviceDMatrix) -> dict:
        """Keywords forwarded to grad/metric fns for one dataset: config
        scalars (traced, so e.g. quantile_alpha changes don't recompile)
        plus the dataset's query groups when present."""
        extra = dict(O.config_kwargs(self.cfg))
        if dmat.group_ids is not None:
            extra["group_ids"] = dmat.group_ids
        return extra

    def fit(
        self,
        dtrain: DeviceDMatrix,
        evals: Sequence = (),
        *,
        obj=None,
        eval_metric=None,
        custom_metric=None,
        early_stopping_rounds: int | None = None,
        verbose_every: int = 0,
        callback: Callable[[int, dict], None] | None = None,
        mesh=None,
        data_axes: Sequence[str] = ("data",),
        collective="psum",
        compression: str | None = None,
        comm_tolerance: float = 0.05,
        checkpoint_every: int | None = None,
        checkpoint_path: str | None = None,
        on_oom: str = "raise",
    ) -> "Booster":
        """Train cfg.n_rounds rounds from scratch on a DeviceDMatrix or an
        ExternalDMatrix (external-memory path: the chunk-stacked compressed
        representation trains through the same compiled scan, bit-identical
        to the in-memory path on the same data — DESIGN.md §11).

        evals: sequence of (DeviceDMatrix, name) pairs (or bare matrices;
          ExternalDMatrix eval sets work too)
          built with `ref=dtrain`; metrics are computed per round inside the
          compiled scan. With `early_stopping_rounds`, the LAST metric of
          the LAST eval set drives stopping (direction = that metric's
          `maximize`) and the ensemble is truncated to best_iteration+1.
        obj: override cfg.objective — a registry name, an Objective (e.g.
          from objectives.register_objective), or a bare callable
          `(margins, y) -> (g, h)` traced into the compiled scan.
        eval_metric: metric spec or list of specs (names like "rmse"/"auc"/
          "ndcg@10", Metric objects, callables) evaluated per round on every
          eval set; defaults to the objective's default metric.
        custom_metric: one extra metric spec (callable or (name, fn[,
          maximize]) tuple), appended after eval_metric.
        mesh: optional jax Mesh — rows are sharded over `data_axes` and
          histograms combined per level (paper Algorithm 1); same Booster out.
        collective: histogram-reduction strategy with mesh= — a registry name
          ("psum" | "ring" | "hier"), a repro.dist.Collective subclass, or an
          instance (DESIGN.md §15). f32 mode trains identically to
          single-device fits for every strategy.
        compression: None | "f16" | "q16" — compressed per-level histogram
          bin sums with an on-device max-error check that falls back to
          exact f32 when `comm_tolerance` (relative) is exceeded. Per-fit
          wire accounting lands on `self.comm_stats`.
        checkpoint_every: write an atomic resumable snapshot every this many
          rounds to `checkpoint_path` (DESIGN.md §13). `Booster.resume(path,
          dtrain)` continues a killed fit to a bit-identical booster.
        checkpoint_path: snapshot file; with checkpoint_every unset, only a
          final complete checkpoint is written there.
        on_oom: "raise" (default) or "external" — on device RESOURCE_EXHAUSTED
          the fit is retried through an ExternalDMatrix with halved
          chunk_rows (repeatedly, until it fits or chunks hit one row).
        """
        if on_oom not in ("raise", "external"):
            raise ValueError(
                f"on_oom must be 'raise' or 'external', got {on_oom!r}"
            )

        def reset():
            self.ensemble = None
            self.history = []
            self.best_iteration = None
            self.best_score = None
            self.n_rounds_trained = 0
            self._margins = None
            self._train_dmat = None
            self.skipped_rounds = []

        reset()
        self.resilience_events = []
        if obj is not None:
            resolved = O.as_objective(obj)
            self._obj = resolved
            self.cfg = dataclasses.replace(self.cfg, objective=resolved.name)
        if dtrain.label is None:
            raise ValueError("dtrain must be constructed with label= to fit")
        self._metrics = self._resolve_metrics(eval_metric, custom_metric)
        with obs.span("fit"):
            self.cuts = dtrain.cuts
            self.base_score = float(self.obj.init_base_score(
                dtrain.label, **O.config_kwargs(self.cfg)
            ))
            dmat = dtrain
            while True:
                try:
                    self._run_rounds(dmat, self.cfg.n_rounds, evals,
                                     early_stopping_rounds, verbose_every,
                                     callback, mesh, data_axes,
                                     checkpoint_every=checkpoint_every,
                                     checkpoint_path=checkpoint_path,
                                     collective=collective,
                                     compression=compression,
                                     comm_tolerance=comm_tolerance)
                    return self
                except Exception as exc:
                    if on_oom != "external" or not RES.is_oom(exc):
                        raise
                    dmat = self._oom_fallback_matrix(dmat, exc)
                    reset()  # drop any partial history before the re-fit

    def _oom_fallback_matrix(self, dmat, exc):
        """Next, smaller-footprint training matrix after a device OOM: an
        in-memory matrix degrades to external memory at half its rows per
        chunk; an external matrix halves chunk_rows again. Re-raises the
        OOM when chunks can no longer shrink."""
        if isinstance(dmat, ExternalDMatrix):
            new_rows = dmat.chunk_rows // 2
            if new_rows < 1:
                raise exc
            nd = dmat.rechunk(new_rows)
        else:
            nd = ExternalDMatrix.from_dmatrix(
                dmat, chunk_rows=max(dmat.n_rows // 2, 1)
            )
        warnings.warn(
            f"device OOM during fit ({str(exc).splitlines()[0][:120]}); "
            f"retrying via external-memory training with "
            f"chunk_rows={nd.chunk_rows} (on_oom='external')"
        )
        self.resilience_events.append({
            "event": "oom_fallback",
            "chunk_rows": int(nd.chunk_rows),
            "error": str(exc)[:200],
        })
        return nd

    def update(
        self,
        dtrain: DeviceDMatrix,
        n_rounds: int,
        evals: Sequence = (),
        *,
        eval_metric=None,
        custom_metric=None,
        early_stopping_rounds: int | None = None,
        verbose_every: int = 0,
        callback: Callable[[int, dict], None] | None = None,
        mesh=None,
        data_axes: Sequence[str] = ("data",),
        collective="psum",
        compression: str | None = None,
        comm_tolerance: float = 0.05,
        checkpoint_every: int | None = None,
        checkpoint_path: str | None = None,
    ) -> "Booster":
        """Continue training for n_rounds more rounds (warm start).

        Re-enters the scan with the existing margins: if `dtrain` is the same
        DeviceDMatrix the booster last trained on, the cached margins are
        reused and the continuation is bit-identical to a single longer fit;
        otherwise margins are rebuilt by on-device binned prediction. The
        objective is fixed at fit time; metrics may be changed per update.
        """
        self._require_fitted()
        if dtrain.label is None:
            raise ValueError("dtrain must be constructed with label= to update")
        if not self._cuts_match(dtrain.cuts):
            raise ValueError(
                "dtrain was quantised with different cuts than this booster; "
                "build it with ref= the original training matrix"
            )
        if eval_metric is not None or custom_metric is not None \
                or self._metrics is None:
            self._metrics = self._resolve_metrics(eval_metric, custom_metric)
        with obs.call("update"):
            self._run_rounds(dtrain, n_rounds, evals, early_stopping_rounds,
                             verbose_every, callback, mesh, data_axes,
                             checkpoint_every=checkpoint_every,
                             checkpoint_path=checkpoint_path,
                             collective=collective, compression=compression,
                             comm_tolerance=comm_tolerance)
        return self

    @classmethod
    def resume(
        cls,
        path: str,
        dtrain,
        evals: Sequence = (),
        *,
        callback: Callable[[int, dict], None] | None = None,
        verbose_every: int | None = None,
        checkpoint_every: int | None = None,
        checkpoint_path: str | None = None,
        mesh=None,
        data_axes: Sequence[str] = ("data",),
        collective="psum",
        compression: str | None = None,
        comm_tolerance: float = 0.05,
    ) -> "Booster":
        """Continue a killed fit from an in-run checkpoint (DESIGN.md §13).

        `dtrain` (and `evals`, same sets in the same order) must be rebuilt
        exactly as for the original fit — the checkpoint carries the model,
        margins, ES state and the absolute-round PRNG anchor, but not the
        data. The resumed booster is bit-identical (trees, margins,
        predictions) to one from an uninterrupted fit: margins re-enter the
        scan exactly as carried, the stochastic key stream folds absolute
        round indices, and ES stop checks fire at the same fit-relative
        boundaries.

        Checkpointing continues with the original cadence to the same file
        by default (override with checkpoint_every/checkpoint_path); the
        file is rewritten as a completed checkpoint when the fit finishes.
        """
        from repro.checkpoint import io as CIO

        bst, rs = CIO.load_booster_with_resume(path)
        if rs is None:
            raise CIO.CheckpointError(
                f"{path} checkpoints a COMPLETED fit (no resume section); "
                "use Booster.load() to load it, or update() to train further"
            )
        try:
            bst._metrics = tuple(
                M.get_metric(n) for n in rs["metric_names"]
            ) or None
        except Exception as exc:
            raise ValueError(
                f"cannot resolve checkpointed eval metrics "
                f"{list(rs['metric_names'])}: {exc}. Re-register custom "
                "metrics (metrics.register_metric) before resuming."
            ) from exc
        if dtrain.label is None:
            raise ValueError("dtrain must be constructed with label= to resume")
        if not bst._cuts_match(dtrain.cuts):
            raise ValueError(
                "dtrain was quantised with different cuts than the "
                "checkpointed fit; rebuild it from the same data with the "
                "same max_bins (or with ref= the original matrix)"
            )
        evals_n = bst._normalise_evals(evals, dtrain)
        names = [n for _, n in evals_n]
        want = [str(n) for n in rs["eval_names"]]
        if names != want:
            raise ValueError(
                f"resume requires the original fit's eval sets in order: "
                f"expected {want}, got {names}"
            )
        remaining = int(rs["target"]) - int(rs["rounds_done"])
        if remaining <= 0:
            return bst
        ve = int(rs.get("verbose_every", 0)) if verbose_every is None \
            else verbose_every
        ck = (int(rs.get("checkpoint_every", 0)) or None) \
            if checkpoint_every is None else checkpoint_every
        cpath = checkpoint_path if checkpoint_path is not None else path
        es = int(rs.get("early_stopping_rounds", 0)) or None
        bst._run_rounds(dtrain, remaining, evals_n, es, ve, callback, mesh,
                        tuple(data_axes), checkpoint_every=ck,
                        checkpoint_path=cpath, resume_state=rs,
                        collective=collective, compression=compression,
                        comm_tolerance=comm_tolerance)
        return bst

    def _cuts_match(self, cuts: jax.Array) -> bool:
        return cuts_equal(self.cuts, cuts)

    def _initial_margins(self, dmat) -> jax.Array:
        """Margins to (re-)enter training with: base score if unfitted, else
        on-device binned prediction of the current ensemble."""
        k = self.obj.n_outputs(self.cfg.n_classes)
        if self.ensemble is None:
            return jnp.full((dmat.n_rows, k), self.base_score, jnp.float32)
        if isinstance(dmat, ExternalDMatrix):
            if dmat.resolved_paging() == "stream":
                # Never page the whole stack in just to rebuild margins:
                # stream chunks through the fused traversal instead
                # (bit-identical to the per-tree chunked scan).
                return self._predict_margins_external(self.ensemble, dmat)
            cpb = dmat.packed_bins()
            return PR.predict_binned_chunked(
                self.ensemble, cpb.packed, cpb.bits, cpb.chunk_rows,
                cpb.n_rows, self.cfg.max_bins - 1, self.cfg.max_depth,
            )
        return PR.predict_binned_packed(
            self.ensemble, dmat.matrix.packed, dmat.bits, dmat.n_rows,
            self.cfg.max_bins - 1, self.cfg.max_depth,
        )

    def _normalise_evals(self, evals, dtrain):
        out = []
        for i, e in enumerate(evals):
            d, name = e if isinstance(e, (tuple, list)) else (e, f"eval{i}")
            if not isinstance(d, (DeviceDMatrix, ExternalDMatrix)):
                raise TypeError(
                    "evals entries must be DeviceDMatrix / ExternalDMatrix "
                    f"(or (matrix, name)), got {type(d)}; build with ref=dtrain"
                )
            if d.label is None:
                raise ValueError(f"eval set '{name}' has no label")
            if not dtrain.same_cuts(d):
                raise ValueError(
                    f"eval set '{name}' was quantised with different cuts; "
                    "build it with DeviceDMatrix(x, label=y, ref=dtrain)"
                )
            out.append((d, name))
        return out

    def _run_rounds(self, dtrain, n_rounds, evals, early_stopping_rounds,
                    verbose_every, callback, mesh, data_axes,
                    checkpoint_every=None, checkpoint_path=None,
                    resume_state=None, collective="psum", compression=None,
                    comm_tolerance=0.05):
        if n_rounds <= 0:
            raise ValueError(f"n_rounds must be positive, got {n_rounds}")
        cfg, obj = self.cfg, self.obj
        if early_stopping_rounds and not evals:
            raise ValueError(
                "early_stopping_rounds requires at least one eval set "
                "(pass evals=[(DeviceDMatrix(..., ref=dtrain), name)])"
            )
        if checkpoint_every is not None:
            if checkpoint_every <= 0:
                raise ValueError(
                    f"checkpoint_every must be positive, got {checkpoint_every}"
                )
            if checkpoint_path is None:
                raise ValueError(
                    "checkpoint_every requires checkpoint_path= (the file "
                    "snapshots are written to)"
                )
        if dtrain.max_bins != cfg.max_bins:
            raise ValueError(
                f"{type(dtrain).__name__} was quantised with "
                f"max_bins={dtrain.max_bins} but this booster expects "
                f"max_bins={cfg.max_bins}; build the matrix with the same "
                "max_bins (bin-space thresholds and the reserved missing bin "
                "must agree)"
            )
        if cfg.monotone_constraints is not None \
                and len(cfg.monotone_constraints) != dtrain.n_features:
            raise ValueError(
                f"monotone_constraints has {len(cfg.monotone_constraints)} "
                f"entries but dtrain has {dtrain.n_features} features"
            )
        evals = self._normalise_evals(evals, dtrain)
        record_every = verbose_every or (1 if (callback or evals) else 0)
        track_metric = record_every > 0
        if self._metrics is None:  # direct _run_rounds callers / legacy paths
            self._metrics = self._resolve_metrics(None, None)
        metrics = self._metrics if track_metric else ()

        y = dtrain.label
        eval_pbs = tuple(d.packed_bins() for d, _ in evals)
        eval_ys = tuple(d.label for d, _ in evals)
        eval_extras = tuple(self._dataset_extra(d) for d, _ in evals)
        if resume_state is not None:
            # Checkpointed margins re-enter the scan exactly as carried —
            # rebuilding them by prediction is NOT bit-identical, so both
            # training and eval margins come from the snapshot verbatim.
            margins = jnp.asarray(resume_state["margins"], jnp.float32)
            eval_margins = tuple(
                jnp.asarray(m, jnp.float32)
                for m in resume_state["eval_margins"]
            )
            done = int(resume_state["rounds_done"])
            rounds_before = int(resume_state["rounds_before"])
            es_history = [float(v) for v in resume_state["es_history"]]
        else:
            if self._train_dmat is dtrain and self._margins is not None:
                margins = self._margins  # exact continuation, same matrix
            else:
                margins = self._initial_margins(dtrain)
            eval_margins = tuple(self._initial_margins(d) for d, _ in evals)
            done = 0
            rounds_before = self.n_rounds_trained  # absolute offset (keys)
            es_history = []
        target = done + n_rounds
        extra = self._dataset_extra(dtrain)
        stoch = SMP.stochastic_params(cfg)
        base_key = jax.random.PRNGKey(cfg.seed) if stoch is not None else None

        if mesh is not None:
            if dtrain.group_ids is not None:
                raise NotImplementedError(
                    "group_ids (rank:pairwise) is single-device only"
                )
            from repro import dist as D

            run_chunk = D.make_chunk_runner(
                cfg, obj, dtrain, mesh, data_axes, eval_pbs, eval_ys,
                eval_extras, metrics, track_metric,
                collective=collective, compression=compression,
                comm_tolerance=comm_tolerance,
            )
        else:
            external = isinstance(dtrain, ExternalDMatrix)
            if cfg.use_kernel_histograms and external:
                raise NotImplementedError(
                    "use_kernel_histograms is not supported with "
                    "ExternalDMatrix (the Pallas kernels are not "
                    "chunk-aware); train with the default builders"
                )
            if external and dtrain.resolved_paging() == "stream":
                # Streaming out-of-core executor (DESIGN.md §17): rounds run
                # eagerly, per-chunk kernels pull from the async prefetch
                # ring; the stack is never device-resident all at once.
                from repro.core import stream as STRM

                run_chunk = STRM.make_stream_runner(
                    cfg, obj, self.cuts, dtrain, y, extra, eval_pbs,
                    eval_ys, eval_extras, metrics, track_metric, base_key,
                )
            else:
                if external:
                    # Resident external-memory path: the chunk-stacked
                    # packed words are the only representation; a dense
                    # matrix never exists.
                    data = dtrain.packed_bins()
                else:
                    data = (
                        dtrain.packed_bins() if cfg.compress_matrix
                        else dtrain.matrix.unpack()
                    )
                hist_builder = None
                if cfg.use_kernel_histograms:
                    from repro.kernels import ops as KO

                    hist_builder = (
                        KO.build_histograms_kernel_packed
                        if cfg.compress_matrix
                        else KO.build_histograms_kernel
                    )
                fns: dict = {}

                def run_chunk(length, start_round, margins, eval_margins):
                    fkey = FA.trace_key("nan_grad")
                    fn = fns.get((length, fkey))
                    if fn is None:
                        fn = fns[(length, fkey)] = _make_train_fn(
                            cfg, obj, self.cuts, hist_builder, metrics,
                            track_metric, n_rounds=length,
                        )
                    if stoch is not None:
                        return fn(base_key,
                                  jnp.asarray(start_round, jnp.int32),
                                  data, margins, y, extra, eval_pbs,
                                  eval_margins, eval_ys, eval_extras)
                    if fkey is not None:
                        return fn(jnp.asarray(start_round, jnp.int32), data,
                                  margins, y, extra, eval_pbs, eval_margins,
                                  eval_ys, eval_extras)
                    return fn(data, margins, y, extra, eval_pbs,
                              eval_margins, eval_ys, eval_extras)

        # Per-fit communication accounting (DESIGN.md §15): analytic wire
        # bytes / collective calls for the chosen strategy, plus the
        # measured compressed-allreduce fallback count (filled post-loop).
        self.comm_stats = (
            run_chunk.comm_stats.as_dict() if mesh is not None else None
        )

        FA.check("oom")
        # The scan runs in compiled chunks delimited by the next early-
        # stopping boundary (multiples of e, one host read per chunk —
        # never per round), the next checkpoint boundary (multiples of
        # checkpoint_every), and the end of the run. Boundaries are FIT-
        # relative, so a resumed fit re-enters the identical chunk schedule
        # and ES decisions replay exactly.
        es_on = bool(early_stopping_rounds) and bool(evals)
        e = int(early_stopping_rounds) if es_on else None
        ck = int(checkpoint_every) if checkpoint_every else None
        eval_names = [name for _, name in evals]
        k = obj.n_outputs(cfg.n_classes)
        run_ens: PR.Ensemble | None = None  # this call's trees, scaled
        best_round: int | None = None
        stopped = False
        last_chunk = None  # (start, tr_host, ev_host) for the final record
        while done < target and not stopped:
            nxt = target
            if es_on:
                nxt = min(nxt, (done // e + 1) * e)
            if ck:
                nxt = min(nxt, (done // ck + 1) * ck)
            length = nxt - done
            with obs.span("round.dispatch"):
                margins, all_trees, tr_metrics, eval_margins, ev_metrics, \
                    flags = run_chunk(length, rounds_before + done, margins,
                                      eval_margins)
            with obs.span("ensemble.append"):
                # The scan's ys-stack IS the ensemble arena: (rounds, k,
                # arena) fields reshaped to XGBoost's round-robin
                # (rounds * k, arena) layout — no per-round host round trips.
                chunk_ens = _scale_leaves(
                    _stack_to_ensemble(all_trees, k, self.base_score),
                    cfg.learning_rate,
                )
                run_ens = chunk_ens if run_ens is None \
                    else PR.concat_ensembles(run_ens, chunk_ens)
            with obs.span("round.host"):
                self._handle_numeric_flags(flags, rounds_before + done)
                tr_host = [np.asarray(v) for v in tr_metrics]
                ev_host = [[np.asarray(v) for v in vals] for vals in ev_metrics]
                if record_every > 0:
                    self._record_history(done, length, tr_host, ev_host, metrics,
                                         eval_names, rounds_before, record_every,
                                         callback)
                last_chunk = (done, tr_host, ev_host)
                self._check_divergence(ev_host, eval_names, metrics,
                                       rounds_before + done)
                if es_on:
                    # The LAST metric of the LAST eval set drives stopping, in
                    # the direction that METRIC declares (XGBoost convention;
                    # the objective itself carries no direction). The stop
                    # check fires only at fit-relative multiples of e (and at
                    # the end), so extra checkpoint boundaries never change the
                    # stopping decision.
                    es_history.extend(ev_host[-1][-1].tolist())
                    if nxt % e == 0 or nxt == target:
                        arr = np.asarray(es_history)
                        best_round = int(np.argmax(arr) if metrics[-1].maximize
                                         else np.argmin(arr))
                        if (len(arr) - 1 - best_round) >= e:
                            stopped = True
                done = nxt
                if ck and not stopped and done < target and done % ck == 0:
                    self._write_checkpoint(
                        checkpoint_path, run_ens=run_ens, done=done,
                        target=target, rounds_before=rounds_before,
                        margins=margins, eval_margins=eval_margins,
                        es_history=es_history, early_stopping_rounds=e,
                        checkpoint_every=ck, verbose_every=verbose_every,
                        eval_names=eval_names,
                    )
        with obs.span("round.wait"):
            jax.block_until_ready(margins)
        if self.comm_stats is not None:
            self.comm_stats["fallback_events"] = int(
                run_chunk.fallback_events
            )

        # Deferred final history record: the cadence above records round r
        # when r % record_every == 0, but the last trained round is recorded
        # unconditionally and is only known once the loop exits.
        if record_every > 0 and last_chunk is not None:
            start, tr_host, ev_host = last_chunk
            final_r = done - 1
            if final_r % record_every != 0:
                with obs.span("round.host"):
                    self._emit_record(final_r, final_r - start, tr_host,
                                      ev_host, metrics, eval_names,
                                      rounds_before, callback)

        keep = best_round + 1 if stopped else done
        with obs.span("ensemble.append"):
            full = run_ens if self.ensemble is None \
                else PR.concat_ensembles(self.ensemble, run_ens)
        if stopped and keep < done:
            # Early stopped: truncate the FULL ensemble to best_iteration+1
            # total rounds (best_round may precede a resume point, so the
            # cut can fall inside the pre-resume trees).
            full = PR.truncate_rounds(full, rounds_before + keep)
        self.ensemble = full
        self.n_rounds_trained = rounds_before + keep
        if es_on and best_round is not None:
            self.best_iteration = rounds_before + best_round
            self.best_score = float(es_history[best_round])
        if keep == done:
            self._margins = margins
            self._train_dmat = dtrain
        else:  # ensemble truncated; cached margins would be stale
            self._margins = None
            self._train_dmat = None
        if checkpoint_path is not None:
            self._write_final_checkpoint(checkpoint_path)

    # --- resilience plumbing (DESIGN.md §13) --------------------------------
    def _record_history(self, start, length, tr_host, ev_host, metrics,
                        eval_names, rounds_before, record_every, callback):
        for i in range(length):
            r = start + i
            if r % record_every:
                continue
            self._emit_record(r, i, tr_host, ev_host, metrics, eval_names,
                              rounds_before, callback)

    def _emit_record(self, r, i, tr_host, ev_host, metrics, eval_names,
                     rounds_before, callback):
        rec: dict[str, Any] = {"round": rounds_before + r}
        for j, m in enumerate(metrics):
            rec[f"train_{m.name}"] = float(tr_host[j][i])
        for name, vals in zip(eval_names, ev_host):
            for j, m in enumerate(metrics):
                rec[f"{name}_{m.name}"] = float(vals[j][i])
        self.history.append(rec)
        if callback:
            callback(rounds_before + r, rec)

    def _handle_numeric_flags(self, flags, start_round):
        """Host-side numeric-sentinel policy, applied once per chunk from
        the per-round finite flags that rode the ys-stack."""
        policy = self.cfg.numeric_check
        if policy == "off" or isinstance(flags, tuple):
            return
        bad = np.flatnonzero(~np.asarray(flags))
        if bad.size == 0:
            return
        rounds = [int(start_round + b) for b in bad]
        if policy == "raise":
            raise RES.NumericError(
                f"non-finite gradients/hessians/leaf values at boosting "
                f"round(s) {rounds} (numeric_check='raise'). Check labels "
                "and objective stability, or train with numeric_check="
                "'warn_skip' or 'clamp'."
            )
        if policy == "warn_skip":
            warnings.warn(
                f"round(s) {rounds} produced non-finite values; their trees "
                "were zeroed and margins carried forward unchanged "
                "(numeric_check='warn_skip')"
            )
            self.skipped_rounds.extend(rounds)
            self.resilience_events.append(
                {"event": "rounds_skipped", "rounds": rounds}
            )
        else:  # clamp
            warnings.warn(
                f"non-finite gradients at round(s) {rounds} were replaced/"
                "clipped before tree growth (numeric_check='clamp')"
            )
            self.resilience_events.append(
                {"event": "gradients_clamped", "rounds": rounds}
            )

    def _check_divergence(self, ev_host, eval_names, metrics, start_round):
        """Divergence detection on eval metrics (active with any non-"off"
        numeric_check): a non-finite metric means later rounds can only
        compound the damage."""
        if self.cfg.numeric_check == "off" or not eval_names:
            return
        for name, vals in zip(eval_names, ev_host):
            for m, arr in zip(metrics, vals):
                bad = np.flatnonzero(~np.isfinite(arr))
                if bad.size == 0:
                    continue
                at = int(start_round + bad[0])
                msg = (f"eval metric {name}_{m.name} became non-finite at "
                       f"round {at} — the fit is diverging")
                if self.cfg.numeric_check == "raise":
                    raise RES.DivergenceError(msg)
                warnings.warn(msg)
                self.resilience_events.append(
                    {"event": "divergence", "metric": f"{name}_{m.name}",
                     "round": at}
                )
                return

    def _write_checkpoint(self, path, *, run_ens, done, target, rounds_before,
                          margins, eval_margins, es_history,
                          early_stopping_rounds, checkpoint_every,
                          verbose_every, eval_names):
        """Atomic in-run snapshot at a chunk boundary: the partial ensemble
        plus everything `resume` needs to replay the rest of the fit
        bit-identically (carried margins, ES history, the absolute-round
        PRNG anchor, and the recording cadence)."""
        from repro.checkpoint import io as CIO

        ens = run_ens if self.ensemble is None \
            else PR.concat_ensembles(self.ensemble, run_ens)
        resume = {
            "rounds_done": int(done),
            "target": int(target),
            "rounds_before": int(rounds_before),
            "margins": margins,
            "eval_margins": tuple(eval_margins),
            "es_history": [float(v) for v in es_history],
            "early_stopping_rounds": int(early_stopping_rounds or 0),
            "checkpoint_every": int(checkpoint_every or 0),
            "verbose_every": int(verbose_every or 0),
            "eval_names": [str(n) for n in eval_names],
            "metric_names": [m.name for m in (self._metrics or ())],
        }
        self._save_snapshot(
            path,
            lambda: CIO.save_booster(
                path, self, ensemble=ens,
                n_rounds_trained=rounds_before + done,
                history=self.history, resume=resume,
            ),
            at_round=rounds_before + done,
        )

    def _write_final_checkpoint(self, path):
        from repro.checkpoint import io as CIO

        self._save_snapshot(path, lambda: CIO.save_booster(path, self),
                            at_round=self.n_rounds_trained)

    def _save_snapshot(self, path, write, at_round):
        """Checkpoint writes retry on transient I/O errors and degrade to a
        warning on persistent failure — losing a snapshot must not kill the
        training run it exists to protect."""
        try:
            RES.with_retries(write, retries=2, backoff=0.05,
                             retry_on=(OSError,))
        except OSError as exc:
            warnings.warn(
                f"checkpoint write to {path} failed after retries ({exc}); "
                "training continues without this snapshot"
            )
            self.resilience_events.append({
                "event": "checkpoint_write_failed", "path": str(path),
                "round": int(at_round), "error": str(exc),
            })

    # --- inference ---------------------------------------------------------
    def predict_margins(
        self, data, iteration_range: tuple[int, int] = (0, 0)
    ) -> jax.Array:
        """Raw margins (n_rows, n_outputs). `data` may be a numpy array, a
        jax array (one float32 conversion, done here and nowhere else) or a
        DeviceDMatrix (bin-space traversal on the packed words — exact, since
        thresholds are cut values and quantisation is searchsorted-left).

        Batch inference runs the fused ensemble traversal (blocks of trees
        evaluated densely over all rows, no per-row gather;
        serve/traversal.py) — bit-identical to the per-tree scan the
        training loop uses.

        iteration_range=(a, b) restricts to boosting rounds [a, b), XGBoost
        semantics (b=0 means "through the last round"); the default is the
        whole model.
        """
        from repro.serve import traversal as ST

        self._require_fitted()
        ens = self.ensemble
        if iteration_range != (0, 0):
            ens = PR.slice_rounds(ens, *iteration_range)
        if isinstance(data, (DeviceDMatrix, ExternalDMatrix)):
            if not self._cuts_match(data.cuts):
                raise ValueError(
                    f"{type(data).__name__} was quantised with different cuts "
                    "than this booster; build it with ref= the training matrix"
                )
            with obs.span("predict.traverse"):
                if isinstance(data, ExternalDMatrix):
                    return self._predict_margins_external(ens, data)
                return ST.predict_margins_fused_packed(
                    ens, data.matrix.packed, data.bits, data.n_rows,
                    self.cfg.max_bins - 1, self.cfg.max_depth,
                )
        with obs.span("predict.put"):
            x = jnp.asarray(data, jnp.float32)
        with obs.span("predict.traverse"):
            return ST.predict_margins_fused(ens, x, self.cfg.max_depth)

    def _predict_margins_external(self, ens, data: ExternalDMatrix):
        """Margins over an ExternalDMatrix by streaming packed chunks
        through the fused traversal one at a time: the full chunk stack is
        never paged in for inference — device transients stay bounded by
        one chunk's words plus one chunk's margins (DESIGN.md §14). When
        training already left the stack device-resident the cached chunks
        are served from it instead of the host."""
        from repro.serve import traversal as ST

        missing_bin = self.cfg.max_bins - 1
        parts = []
        for words in data.iter_device_chunks():
            parts.append(ST.predict_margins_fused_packed(
                ens, words, data.bits, data.chunk_rows, missing_bin,
                self.cfg.max_depth,
            ))
        return jnp.concatenate(parts, axis=0)[: data.n_rows]

    def predict(
        self, data, output_margin: bool = False,
        iteration_range: tuple[int, int] = (0, 0),
    ) -> jax.Array:
        """Transformed predictions (probabilities / values / class ids) —
        the model knows its own objective, depth and class count.
        output_margin / iteration_range follow XGBoost's predict knobs."""
        with obs.span("predict"):
            m = self.predict_margins(data, iteration_range=iteration_range)
            if output_margin:
                return m
            with obs.span("predict.transform"):
                return self.obj.transform(m)

    def eval(self, dmat: DeviceDMatrix, name: str = "eval",
             metrics=None) -> dict:
        """One-shot metrics on a labelled DeviceDMatrix.

        metrics: optional spec or list of specs (as in fit's eval_metric);
        defaults to the objective's default metric. Returns
        {f"{name}_{metric}": value} for each metric.
        """
        self._require_fitted()
        if dmat.label is None:
            raise ValueError("eval requires a labelled DeviceDMatrix")
        resolved = M.resolve_metrics(metrics) or (
            M.get_metric(self.obj.default_metric),
        )
        margins = self.predict_margins(dmat)
        extra = self._dataset_extra(dmat)
        return {
            f"{name}_{m.name}": float(m.fn(margins, dmat.label, **extra))
            for m in resolved
        }

    def feature_importances(self, importance_type: str = "gain") -> np.ndarray:
        """Per-feature importance over the fitted ensemble, from the split
        gains stored in the tree arenas (a split node is any arena slot
        with finite gain; leaves and inactive slots carry -inf).

        importance_type:
          * "gain"       — mean objective reduction per split on the feature
                           (XGBoost's default importance_type);
          * "total_gain" — summed objective reduction;
          * "weight"     — number of splits on the feature.

        Returns a float64 (n_features,) vector (unnormalised — the sklearn
        estimators' `feature_importances_` normalises to sum 1). Boosters
        loaded from checkpoints that predate stored gains report zeros.
        """
        self._require_fitted()
        gain = np.asarray(self.ensemble.gain, np.float64)
        feat = np.asarray(self.ensemble.feature)
        split = np.isfinite(gain)
        n_features = self.cuts.shape[0]
        counts = np.bincount(
            feat[split], minlength=n_features
        ).astype(np.float64)
        if importance_type == "weight":
            return counts
        if importance_type in ("gain", "total_gain"):
            total = np.zeros(n_features, np.float64)
            np.add.at(total, feat[split], gain[split])
            if importance_type == "total_gain":
                return total
            return np.divide(total, counts, out=np.zeros_like(total),
                             where=counts > 0)
        raise ValueError(
            f"importance_type must be 'gain', 'total_gain' or 'weight', "
            f"got {importance_type!r}"
        )

    # --- persistence -------------------------------------------------------
    def save(self, path: str) -> None:
        """Self-describing checkpoint (config + cuts + base score + trees)
        via the msgpack layer, with a versioned metadata header."""
        self._require_fitted()
        from repro.checkpoint import io as CIO

        CIO.save_booster(path, self)

    @classmethod
    def load(cls, path: str) -> "Booster":
        from repro.checkpoint import io as CIO

        return CIO.load_booster(path)


# Deprecated alias: the old TrainState (ensemble/margins/matrix/history
# attribute surface) is now the Booster itself.
TrainState = Booster


def train(
    x: np.ndarray | jax.Array,
    y: np.ndarray | jax.Array,
    cfg: BoosterConfig,
    eval_set: tuple[Any, Any] | None = None,
    group_ids: np.ndarray | None = None,
    verbose_every: int = 0,
    callback: Callable[[int, dict], None] | None = None,
) -> Booster:
    """Deprecated one-shot shim over DeviceDMatrix + Booster.fit.

    Re-quantises x on every call — build a DeviceDMatrix once and call
    `Booster.fit` to amortise that. `eval_set` is routed through the in-scan
    eval path, so history records are honest per-round entries.
    """
    dtrain = DeviceDMatrix(x, label=y, group_ids=group_ids,
                           max_bins=cfg.max_bins)
    evals = []
    if eval_set is not None:
        xv, yv = eval_set
        evals.append((DeviceDMatrix(xv, label=yv, ref=dtrain), "valid"))
    return Booster(cfg).fit(dtrain, evals=evals, verbose_every=verbose_every,
                            callback=callback)


def predict_margins(ens: PR.Ensemble, x, max_depth: int) -> jax.Array:
    """Deprecated shim: raw-threshold margins. The single float32 conversion
    lives here (predict() does not convert again)."""
    return PR.predict_raw(ens, jnp.asarray(x, jnp.float32), max_depth)


def predict(ens: PR.Ensemble, x, max_depth: int, objective: str) -> jax.Array:
    """Deprecated shim: prefer Booster.predict (no per-call max_depth /
    objective — the model describes itself)."""
    obj = O.get_objective(objective)
    return obj.transform(predict_margins(ens, x, max_depth))
