"""DeviceDMatrix — the quantised, compressed training matrix as a first-class
user-facing object (paper Figure 1, left boxes; XGBoost's `DMatrix` noun).

Construction runs the paper's preprocessing pipeline ONCE on device:
quantile generation (`compute_cuts`) -> quantisation (`quantize`) ->
bit-packed compression (`compress`). The resulting object is the durable
on-device artifact: it can be reused across any number of `Booster.fit` /
`Booster.update` calls without re-quantising, and it is the only training-set
representation the booster ever sees (the raw float matrix can be freed by
the caller immediately after construction).

Evaluation sets must share the training matrix's cut points so that
bin-space tree traversal agrees exactly with raw-threshold traversal
(threshold == cuts[feature, split_bin] and `quantize` uses
searchsorted-left, so `x <= threshold  <=>  bin <= split_bin`). Build them
with `ref=`, mirroring XGBoost's `QuantileDMatrix(..., ref=dtrain)`:

    dtrain = DeviceDMatrix(x_train, label=y_train)
    dvalid = DeviceDMatrix(x_valid, label=y_valid, ref=dtrain)

Two batch-iterator constructors remove the all-resident-at-once ceiling
(DESIGN.md §11):

  * `DeviceDMatrix.from_batches(batches)` assembles the SAME in-memory
    matrix from an iterator of chunks (bit-identical to constructing from
    the concatenated array) — convenience for sources that are naturally
    chunked but still fit on device.
  * `ExternalDMatrix(batches, chunk_rows=...)` never builds the flat
    matrix at all: cut points stream through a quantile sketch, every
    chunk is quantised + bit-packed independently, and the chunks live
    host-side until training pages the compressed stack in. Training over
    it scans chunk-by-chunk, bounding dense device transients by one chunk.
"""
from __future__ import annotations

import queue
import threading
import warnings

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import compress as C
from repro.core import quantile as Q
from repro.core import resilience as RES
from repro.testing import faults as FA


def _split_batch_item(item, index: int):
    """One iterator item -> (x, label | None, group_ids | None)."""
    if isinstance(item, (tuple, list)):
        if not 1 <= len(item) <= 3:
            raise ValueError(
                f"batch {index}: expected x, (x, y) or (x, y, group_ids), "
                f"got a {len(item)}-tuple"
            )
        return tuple(item) + (None,) * (3 - len(item))
    return item, None, None


def _collect_batches(batches):
    """Validate and materialise a batch iterator as host float32 chunks.

    Every chunk must be a 2-D numeric array with the same n_features and
    the same dtype as the first chunk, and labels/group_ids must be present
    either for every chunk or for none, with lengths matching their chunk —
    anything else raises a ValueError naming the offending batch (instead
    of an opaque XLA shape error deep inside quantise/compress).

    Returns (x_chunks, label or None, group_ids or None, n_features).
    """
    xs, ys, gs = [], [], []
    n_features = None
    dtype0 = None
    for i, item in enumerate(batches):
        x, y, g = _split_batch_item(item, i)
        x = np.asarray(x)
        if x.dtype == object or not (
            np.issubdtype(x.dtype, np.number) or x.dtype == np.bool_
        ):
            raise ValueError(
                f"batch {i} has non-numeric dtype {x.dtype!r}; batches must "
                "be numeric 2-D arrays"
            )
        if x.ndim != 2:
            raise ValueError(
                f"batch {i} must be 2-D (rows, n_features), got shape {x.shape}"
            )
        if x.shape[0] == 0:
            raise ValueError(f"batch {i} is empty (0 rows)")
        if x.shape[1] == 0:
            raise ValueError(f"batch {i} has 0 features")
        if n_features is None:
            n_features, dtype0 = x.shape[1], x.dtype
        else:
            if x.shape[1] != n_features:
                raise ValueError(
                    f"batch {i} has {x.shape[1]} features but batch 0 had "
                    f"{n_features}; all batches must agree"
                )
            if x.dtype != dtype0:
                raise ValueError(
                    f"batch {i} has dtype {x.dtype!r} but batch 0 had "
                    f"{dtype0!r}; all batches must agree"
                )
        if (y is None) != (not ys) and i > 0:
            raise ValueError(
                f"batch {i} {'has no label but earlier batches did' if y is None else 'has a label but earlier batches did not'}"
                "; labels must be given for every batch or for none"
            )
        if y is not None:
            y = np.asarray(y, np.float32).reshape(-1)
            if y.shape[0] != x.shape[0]:
                raise ValueError(
                    f"batch {i}: label has {y.shape[0]} rows, x has {x.shape[0]}"
                )
            if not np.isfinite(y).all():
                raise ValueError(
                    f"batch {i}: label contains non-finite values (NaN/inf); "
                    "clean or drop those rows before training"
                )
            ys.append(y)
        if (g is None) != (not gs) and i > 0:
            raise ValueError(
                f"batch {i}: group_ids must be given for every batch or none"
            )
        if g is not None:
            g = np.asarray(g, np.int32).reshape(-1)
            if g.shape[0] != x.shape[0]:
                raise ValueError(
                    f"batch {i}: group_ids has {g.shape[0]} rows, "
                    f"x has {x.shape[0]}"
                )
            gs.append(g)
        xf = np.ascontiguousarray(x, np.float32)
        if np.isinf(xf).any():
            raise ValueError(
                f"batch {i} contains infinite feature values; replace ±inf "
                "with NaN (legal missing marker) or a large finite value "
                "before quantisation"
            )
        xs.append(xf)
    if not xs:
        raise ValueError("batch iterator produced no batches")
    label = np.concatenate(ys) if ys else None
    groups = np.concatenate(gs) if gs else None
    return xs, label, groups, n_features


def _push_chunk_sorted(sk: "Q.StreamingQuantileSketch", chunk: np.ndarray) -> None:
    """Fold one host chunk into a sketch via the sorted fast path.

    One column-wise np.sort + push_sorted replaces the per-feature host
    argsort loop that push() runs — same summaries, same cuts (push_sorted
    is exactly push for unit weights), a large constant factor cheaper on
    wide chunks. NaNs (the only non-finite values _collect_batches admits)
    are filled with +inf so they sort to the tail, matching push_sorted's
    input contract; n_valid counts the finite prefix per column.
    """
    filled = np.where(np.isnan(chunk), np.inf, chunk)
    cols = np.sort(filled, axis=0)
    n_valid = np.isfinite(cols).sum(axis=0)
    sk.push_sorted(cols, n_valid)


def cuts_equal(a: jax.Array | None, b: jax.Array | None) -> bool:
    """Identity-or-value equality of two cut-point arrays — the single
    definition used by both DeviceDMatrix and Booster validation."""
    if a is b:
        return True
    if a is None or b is None:
        return False
    return a.shape == b.shape and bool(jnp.all(a == b))


class DeviceDMatrix:
    """Device-resident quantised + compressed data matrix.

    Args:
      x: (n_rows, n_features) float array (numpy or jax), NaN = missing.
      label: optional (n_rows,) targets; required for `Booster.fit`.
      group_ids: optional (n_rows,) int query-group ids (rank:pairwise).
      max_bins: total bins per feature incl. the reserved missing bin.
      ref: another DeviceDMatrix whose cut points (and max_bins) to reuse —
        required for evaluation sets so bin-space traversal is exact.
      cuts: optional precomputed (n_features, n_value_bins - 1) cut array —
        e.g. from `repro.dist.sharded_sketch_cuts` (device-sharded sketch
        build, paper §quantiles). Mutually exclusive with `ref`.
    """

    def __init__(
        self,
        x,
        label=None,
        *,
        group_ids=None,
        max_bins: int = Q.DEFAULT_MAX_BINS,
        ref: "DeviceDMatrix | None" = None,
        cuts=None,
    ):
        x = jnp.asarray(x, jnp.float32)
        if x.ndim != 2:
            raise ValueError(f"x must be 2-D (n_rows, n_features), got {x.shape}")
        if x.shape[0] == 0:
            raise ValueError(
                "x has 0 rows; cannot build a DeviceDMatrix from an empty "
                "matrix"
            )
        if x.shape[1] == 0:
            raise ValueError(
                "x has 0 features; every row needs at least one feature "
                "column"
            )
        if bool(jnp.any(jnp.isinf(x))):
            raise ValueError(
                "x contains infinite feature values; replace ±inf with NaN "
                "(the legal missing marker) or a large finite value before "
                "quantisation"
            )
        if ref is not None:
            if cuts is not None:
                raise ValueError(
                    "pass either ref= or cuts=, not both (ref already "
                    "carries its cut points)"
                )
            cuts = ref.cuts
            max_bins = ref.max_bins
            if x.shape[1] != ref.n_features:
                raise ValueError(
                    f"ref has {ref.n_features} features, x has {x.shape[1]}"
                )
        elif cuts is not None:
            cuts = jnp.asarray(cuts, jnp.float32)
            nvb = Q.n_value_bins(max_bins)
            if cuts.shape != (x.shape[1], nvb - 1):
                raise ValueError(
                    f"cuts must have shape ({x.shape[1]}, {nvb - 1}) for "
                    f"max_bins={max_bins}, got {cuts.shape}"
                )
        else:
            cuts = Q.compute_cuts(x, max_bins)
        bins = Q.quantize(x, cuts)
        self.matrix: C.CompressedMatrix = C.compress(bins, cuts, max_bins)
        self.label = None if label is None else jnp.asarray(label, jnp.float32)
        self.group_ids = (
            None if group_ids is None else jnp.asarray(group_ids, jnp.int32)
        )
        # Per-shard re-packings placed on a mesh (`sharded_packed`), keyed
        # by sharding — paid once per (matrix, mesh), not per fit.
        self._shard_pack_cache: dict = {}
        if self.label is not None and self.label.shape[0] != self.n_rows:
            raise ValueError(
                f"label has {self.label.shape[0]} rows, x has {self.n_rows}"
            )
        if self.label is not None and \
                not bool(jnp.all(jnp.isfinite(self.label))):
            raise ValueError(
                "label contains non-finite values (NaN/inf); clean or drop "
                "those rows before training"
            )

    @classmethod
    def from_batches(
        cls,
        batches,
        *,
        max_bins: int = Q.DEFAULT_MAX_BINS,
        ref: "DeviceDMatrix | None" = None,
    ) -> "DeviceDMatrix":
        """Build the in-memory matrix from an iterator of chunks.

        `batches` yields `x`, `(x, y)` or `(x, y, group_ids)` chunks; they
        are validated (consistent n_features/dtype, matching label lengths
        — a clear ValueError instead of an opaque XLA error) and assembled
        into exactly the matrix `DeviceDMatrix(concat(chunks), ...)` would
        produce, bit for bit. For data that must never be resident all at
        once, use `ExternalDMatrix` instead.
        """
        xs, label, groups, _ = _collect_batches(batches)
        x = xs[0] if len(xs) == 1 else np.concatenate(xs)
        return cls(x, label=label, group_ids=groups, max_bins=max_bins,
                   ref=ref)

    # --- surface -----------------------------------------------------------
    @property
    def cuts(self) -> jax.Array:
        return self.matrix.cuts

    @property
    def max_bins(self) -> int:
        return self.matrix.max_bins

    @property
    def bits(self) -> int:
        return self.matrix.bits

    @property
    def n_rows(self) -> int:
        return self.matrix.n_rows

    @property
    def n_features(self) -> int:
        return self.matrix.n_features

    @property
    def nbytes(self) -> int:
        """Device bytes held: packed words + cut points + labels/groups."""
        total = self.matrix.nbytes_compressed() + int(np.prod(self.cuts.shape)) * 4
        if self.label is not None:
            total += self.label.shape[0] * 4
        if self.group_ids is not None:
            total += self.group_ids.shape[0] * 4
        return total

    def packed_bins(self) -> C.PackedBins:
        """The traced (jit-flowable) view consumed by the training scan."""
        return self.matrix.as_packed_bins()

    def sharded_packed(self, n_shards: int, sharding) -> jax.Array:
        """The packed words re-packed per row shard, so each shard's words
        decode independently, and placed with `sharding` (words axis split
        over the data axes) — the matrix a row-sharded fit trains on.
        Cached per sharding: the dense-bins transient (the matrix DESIGN.md
        §2 bans from steady state) exists once per mesh, not once per fit.
        """
        data = self._shard_pack_cache.get(sharding)
        if data is None:
            bins = self.matrix.unpack()
            n_per = self.n_rows // n_shards
            data = jnp.concatenate(
                [C.pack(bins[i * n_per : (i + 1) * n_per], self.bits)
                 for i in range(n_shards)],
                axis=1,
            )  # (F, n_shards * W)
            data = self._shard_pack_cache[sharding] = jax.device_put(
                data, sharding
            )
        return data

    def compression_ratio(self) -> float:
        return self.matrix.compression_ratio()

    def same_cuts(self, other: "DeviceDMatrix") -> bool:
        return cuts_equal(self.cuts, other.cuts)

    def __repr__(self) -> str:  # pragma: no cover - cosmetic
        return (
            f"DeviceDMatrix({self.n_rows}x{self.n_features}, "
            f"{self.bits}-bit, max_bins={self.max_bins}, "
            f"{self.nbytes / 1e6:.2f} MB"
            f"{', labelled' if self.label is not None else ''})"
        )


class ChunkPager:
    """Bounded background prefetcher over a sequence of chunk indices.

    A daemon thread walks `indices`, calls `load_fn(i)` for each (the
    host->device staging step — crc verify + `jnp.asarray` transfer), and
    parks the results in a queue of at most `depth` staged chunks. The
    consumer iterates `(index, chunk)` pairs: while it computes on chunk k,
    the worker is already transferring chunk k+1 (double-buffered at
    depth=2), hiding host->device latency behind compute. XLA dispatch and
    the crc32 both release the GIL, so the overlap is genuine even on CPU.

    `depth <= 0` (or a single chunk) degrades to a plain synchronous loop
    — same yields, same order, no thread — which is the bit-identity
    anchor: the consumer's arithmetic never depends on the staging mode.

    Exceptions raised by `load_fn` (after its own retry policy is
    exhausted) are forwarded through the queue and re-raised in the
    consumer; the worker stops producing past a failure so a broken source
    cannot keep filling the ring. `close()` (called automatically when
    iteration ends, breaks, or raises) stops the worker and drains the
    queue so blocked puts can observe the stop flag.
    """

    def __init__(self, load_fn, indices, depth: int):
        self._load = load_fn
        self._indices = list(indices)
        self._queue: queue.Queue | None = None
        self._stop: threading.Event | None = None
        self._thread: threading.Thread | None = None
        if depth > 0 and len(self._indices) > 1:
            self._queue = queue.Queue(maxsize=depth)
            self._stop = threading.Event()
            self._thread = threading.Thread(
                target=self._worker, name="chunk-pager", daemon=True
            )
            self._thread.start()

    def _worker(self) -> None:
        for i in self._indices:
            if self._stop.is_set():
                return
            try:
                item = (i, self._load(i), None)
            except BaseException as exc:  # forwarded, not swallowed
                item = (i, None, exc)
            while not self._stop.is_set():
                try:
                    self._queue.put(item, timeout=0.1)
                    break
                except queue.Full:
                    continue
            if item[2] is not None:
                return

    def __iter__(self):
        try:
            if self._thread is None:
                for i in self._indices:
                    yield i, self._load(i)
                return
            for _ in self._indices:
                i, chunk, exc = self._queue.get()
                if exc is not None:
                    raise exc
                yield i, chunk
        finally:
            self.close()

    def close(self) -> None:
        """Stop the worker and release staged chunks (idempotent)."""
        if self._thread is not None:
            self._stop.set()
            try:
                while True:
                    self._queue.get_nowait()
            except queue.Empty:
                pass
            self._thread.join()
            self._thread = None
            self._queue = None

    def __enter__(self) -> "ChunkPager":
        return self

    def __exit__(self, *exc) -> bool:
        self.close()
        return False


def _normalize_verify(verify) -> str:
    """verify_chunks knob -> one of 'once' | 'always' | 'never'."""
    if verify is True:
        return "once"
    if verify is False:
        return "never"
    if verify in ("once", "always", "never"):
        return verify
    raise ValueError(
        "verify_chunks must be True ('once'), False ('never'), 'once', "
        f"'always' or 'never', got {verify!r}"
    )


class ExternalDMatrix:
    """External-memory training matrix: host-resident bit-packed chunks.

    The flat (n_rows, n_features) matrix never exists on device — not as
    floats, not as dense bins. Cut points come from a streaming quantile
    sketch (one pass over the chunks, bounded memory), each chunk is then
    quantised and bit-packed independently, and the packed chunks are kept
    host-side as one (n_chunks, n_features, words_per_chunk) uint32 stack.
    `packed_bins()` pages the compressed stack onto the device (cached;
    `unload()` drops it again) as a `ChunkedPackedBins` pytree that the
    booster's compiled scan consumes chunk by chunk, so dense device
    transients stay bounded by one chunk regardless of n_rows
    (DESIGN.md §11).

    Labels, group ids and per-round gradients stay fully device-resident
    (they are O(n), the matrix is O(n * f) — the same split XGBoost's
    external-memory mode makes).

    Args:
      batches: iterator of `x`, `(x, y)` or `(x, y, group_ids)` chunks
        (validated like `DeviceDMatrix.from_batches`; incoming chunk sizes
        are arbitrary — rows are re-chunked to `chunk_rows`).
      chunk_rows: rows per stored chunk — the unit of device paging and the
        bound on dense transients during construction and training.
      max_bins: total bins per feature incl. the reserved missing bin.
      ref: reuse another matrix's cut points (evaluation sets; overrides
        `cuts`).
      cuts: "sketch" (default — stream a StreamingQuantileSketch over the
        chunks), "exact" (gather the full float matrix once and run
        `compute_cuts`; bit-identical to the in-memory matrix, for
        artificially chunked data and parity testing), or a precomputed
        (n_features, n_value_bins - 1) cut array.
      sketch_capacity: per-feature summary size for cuts="sketch".
      sketch_shards: with cuts="sketch", build one sketch per shard of the
        chunk list and combine them by repro.dist's log-depth tree merge
        (the paper's distributed sketch build) instead of one sequential
        fold — fewer prune rounds on any leaf-to-root path, and the
        host-side analogue of the device-sharded build
        (`repro.dist.sharded_sketch_cuts`). 1 (default) keeps the
        sequential stream.
      verify_chunks: crc32 verification policy for page-in (crcs are
        recorded at build so bit-flips between build and load surface as a
        ChunkIntegrityError instead of silently training on garbage,
        DESIGN.md §13). True or "once" (default): each chunk is verified
        the first time it is paged in and re-verified after any load
        retry, then trusted — steady-state epochs pay zero checksum cost.
        "always": re-verify on every page-in (paranoid mode for flaky
        storage). False or "never": skip verification entirely.
      load_retries / load_backoff: transient page-in failures (I/O errors,
        integrity failures in the transfer path) are retried this many
        times with exponential backoff before the error propagates.
      paging: "resident" pages the whole compressed stack to device once
        and trains on the compiled chunked scan; "stream" keeps the stack
        host-side and streams chunks through a bounded prefetching pager
        every round (device footprint ~prefetch_chunks+1 chunks instead of
        the full stack — for stacks that do not fit device memory);
        "auto" (default) picks "stream" only when the device reports a
        memory limit and the stack would occupy more than half of it,
        otherwise "resident" (DESIGN.md §17).
      prefetch_chunks: staged-chunk ring depth for streamed paging — the
        worker thread keeps up to this many chunks in flight ahead of
        compute (2 = double buffering). 0 disables the background thread
        (synchronous loads, bit-identical results).
    """

    def __init__(
        self,
        batches,
        *,
        chunk_rows: int = 131072,
        max_bins: int = Q.DEFAULT_MAX_BINS,
        ref=None,
        cuts="sketch",
        sketch_capacity: int = 1024,
        sketch_shards: int = 1,
        verify_chunks: bool | str = True,
        load_retries: int = 2,
        load_backoff: float = 0.05,
        paging: str = "auto",
        prefetch_chunks: int = 2,
    ):
        if chunk_rows <= 0:
            raise ValueError(f"chunk_rows must be positive, got {chunk_rows}")
        if paging not in ("auto", "resident", "stream"):
            raise ValueError(
                f"paging must be 'auto', 'resident' or 'stream', got {paging!r}"
            )
        if prefetch_chunks < 0:
            raise ValueError(
                f"prefetch_chunks must be >= 0, got {prefetch_chunks}"
            )
        xs, label, groups, n_features = _collect_batches(batches)
        n_rows = sum(c.shape[0] for c in xs)
        xs = _rechunk(xs, chunk_rows)

        if ref is not None:
            if n_features != ref.n_features:
                raise ValueError(
                    f"ref has {ref.n_features} features, batches have "
                    f"{n_features}"
                )
            cut_arr = ref.cuts
            max_bins = ref.max_bins
        elif isinstance(cuts, str):
            if cuts == "exact":
                cut_arr = Q.compute_cuts(
                    jnp.asarray(np.concatenate(xs)), max_bins
                )
            elif cuts == "sketch":
                if sketch_shards < 1:
                    raise ValueError(
                        f"sketch_shards must be >= 1, got {sketch_shards}"
                    )
                shards = min(sketch_shards, len(xs))
                if shards > 1:
                    # Distributed-style build: one sketch per chunk shard,
                    # combined by log-depth tree merge (repro.dist.sketch).
                    from repro.dist.sketch import tree_merge

                    sketches = []
                    for s in range(shards):
                        sk = Q.StreamingQuantileSketch(
                            n_features, max_bins, capacity=sketch_capacity
                        )
                        for chunk in xs[s::shards]:
                            _push_chunk_sorted(sk, chunk)
                        sketches.append(sk)
                    cut_arr = tree_merge(sketches).get_cuts()
                else:
                    sketch = Q.StreamingQuantileSketch(
                        n_features, max_bins, capacity=sketch_capacity
                    )
                    for chunk in xs:
                        _push_chunk_sorted(sketch, chunk)
                    cut_arr = sketch.get_cuts()
            else:
                raise ValueError(
                    f"cuts must be 'sketch', 'exact' or an array, got {cuts!r}"
                )
        else:
            cut_arr = jnp.asarray(cuts, jnp.float32)
            nvb = Q.n_value_bins(max_bins)
            if cut_arr.shape != (n_features, nvb - 1):
                raise ValueError(
                    f"cuts must have shape ({n_features}, {nvb - 1}), "
                    f"got {cut_arr.shape}"
                )

        # Quantise + pack chunk by chunk: the dense transients (float chunk,
        # int32 bin chunk) are bounded by chunk_rows. Bit width is fixed
        # from max_bins so every chunk packs identically without a second
        # global pass over the data.
        bits = C.bits_needed(max_bins - 1)
        spw = C.symbols_per_word(bits)
        words_per_chunk = -(-chunk_rows // spw)
        host_chunks = np.zeros(
            (len(xs), n_features, words_per_chunk), np.uint32
        )
        for i, chunk in enumerate(xs):
            bins = Q.quantize(jnp.asarray(chunk), cut_arr)
            packed = np.asarray(C.pack(bins, bits))
            host_chunks[i, :, : packed.shape[1]] = packed

        self._host_packed = host_chunks
        self._device_stack: jax.Array | None = None
        self.cuts = cut_arr
        self.max_bins = max_bins
        self.bits = bits
        self.chunk_rows = chunk_rows
        self.n_rows = n_rows
        self.label = None if label is None else jnp.asarray(label, jnp.float32)
        self.group_ids = (
            None if groups is None else jnp.asarray(groups, jnp.int32)
        )
        self.verify_chunks = _normalize_verify(verify_chunks)
        self.load_retries = load_retries
        self.load_backoff = load_backoff
        self.paging = paging
        self.prefetch_chunks = prefetch_chunks
        self._chunk_crcs = RES.crc32_chunks(host_chunks)
        self._verified = np.zeros(host_chunks.shape[0], np.bool_)
        self.stream_stats = None  # last streamed fit's counters (stream.py)

    @classmethod
    def from_dmatrix(cls, dmat: "DeviceDMatrix", *, chunk_rows: int,
                     **kw) -> "ExternalDMatrix":
        """Convert an in-memory DeviceDMatrix to external memory — the
        `fit(on_oom="external")` degradation path. Bins are recovered from
        the packed words and re-chunked; no raw float matrix is needed, the
        cut points are shared, and training on the result is bit-identical
        to the in-memory matrix (DESIGN.md §11)."""
        bins = np.asarray(dmat.matrix.unpack())
        return cls._from_host_bins(bins, dmat.cuts, dmat.max_bins,
                                   dmat.label, dmat.group_ids, chunk_rows,
                                   **kw)

    @classmethod
    def _from_host_bins(cls, bins, cuts, max_bins, label, group_ids,
                        chunk_rows, *, verify_chunks: bool | str = True,
                        load_retries: int = 2, load_backoff: float = 0.05,
                        paging: str = "auto", prefetch_chunks: int = 2):
        """Build from already-quantised host bins (from_dmatrix / rechunk):
        the float->bins pipeline is skipped, everything downstream of
        quantisation is identical to __init__."""
        if chunk_rows <= 0:
            raise ValueError(f"chunk_rows must be positive, got {chunk_rows}")
        self = cls.__new__(cls)
        n_rows, n_features = bins.shape
        bits = C.bits_needed(max_bins - 1)
        spw = C.symbols_per_word(bits)
        words_per_chunk = -(-chunk_rows // spw)
        n_chunks = -(-n_rows // chunk_rows)
        host_chunks = np.zeros(
            (n_chunks, n_features, words_per_chunk), np.uint32
        )
        for i, s in enumerate(range(0, n_rows, chunk_rows)):
            packed = np.asarray(
                C.pack(jnp.asarray(bins[s : s + chunk_rows]), bits)
            )
            host_chunks[i, :, : packed.shape[1]] = packed
        self._host_packed = host_chunks
        self._device_stack = None
        self.cuts = cuts
        self.max_bins = max_bins
        self.bits = bits
        self.chunk_rows = chunk_rows
        self.n_rows = n_rows
        self.label = None if label is None else jnp.asarray(label, jnp.float32)
        self.group_ids = (
            None if group_ids is None else jnp.asarray(group_ids, jnp.int32)
        )
        self.verify_chunks = _normalize_verify(verify_chunks)
        self.load_retries = load_retries
        self.load_backoff = load_backoff
        self.paging = paging
        self.prefetch_chunks = prefetch_chunks
        self._chunk_crcs = RES.crc32_chunks(host_chunks)
        self._verified = np.zeros(n_chunks, np.bool_)
        self.stream_stats = None  # last streamed fit's counters (stream.py)
        return self

    def rechunk(self, chunk_rows: int) -> "ExternalDMatrix":
        """A new ExternalDMatrix over the same data with a different chunk
        size (the OOM path halves chunk_rows until the fit fits). Chunks
        are decoded host-side and re-packed; cuts, labels and groups are
        shared, so training stays bit-identical."""
        return type(self)._from_host_bins(
            self._decode_host_bins(), self.cuts, self.max_bins, self.label,
            self.group_ids, chunk_rows, verify_chunks=self.verify_chunks,
            load_retries=self.load_retries, load_backoff=self.load_backoff,
            paging=self.paging, prefetch_chunks=self.prefetch_chunks,
        )

    def _decode_host_bins(self) -> np.ndarray:
        """The dense bins matrix, host-side (transient: only rechunk and
        parity tests materialise it)."""
        out = np.empty((self.n_rows, self.n_features), np.int32)
        for i in range(self.n_chunks):
            s = i * self.chunk_rows
            rows = min(self.chunk_rows, self.n_rows - s)
            out[s : s + rows] = np.asarray(
                C.unpack(jnp.asarray(self._host_packed[i]), self.bits, rows)
            )
        return out

    @classmethod
    def from_arrays(
        cls, x, label=None, *, group_ids=None, chunk_rows: int = 131072, **kw
    ) -> "ExternalDMatrix":
        """Artificially chunk an in-memory array (tests, benchmarks, and
        the parity check against `DeviceDMatrix`)."""
        x = np.asarray(x, np.float32)

        def batches():
            for s in range(0, x.shape[0], chunk_rows):
                xb = x[s : s + chunk_rows]
                yb = None if label is None else np.asarray(label)[s : s + chunk_rows]
                gb = None if group_ids is None else np.asarray(group_ids)[s : s + chunk_rows]
                if gb is not None:
                    yield xb, yb, gb
                elif yb is not None:
                    yield xb, yb
                else:
                    yield xb
        return cls(batches(), chunk_rows=chunk_rows, **kw)

    # --- surface -----------------------------------------------------------
    @property
    def n_chunks(self) -> int:
        return self._host_packed.shape[0]

    @property
    def n_features(self) -> int:
        return self._host_packed.shape[1]

    @property
    def nbytes_host(self) -> int:
        """Host bytes held by the packed chunk stack."""
        return self._host_packed.nbytes

    @property
    def nbytes_device(self) -> int:
        """Device bytes currently held (0 when paged out)."""
        if self._device_stack is None:
            return 0
        return int(np.prod(self._device_stack.shape)) * 4

    def resolved_paging(self) -> str:
        """The effective paging mode: "resident" or "stream".

        "auto" resolves to "stream" only when the backing device reports a
        memory limit and the compressed stack would occupy more than half
        of it (leaving headroom for gradients, histograms and transients);
        anywhere the limit is unknown — CPU backends report no memory
        stats — it resolves to "resident", the proven compiled-scan path.
        """
        if self.paging != "auto":
            return self.paging
        stats = jax.devices()[0].memory_stats()
        limit = (stats or {}).get("bytes_limit")
        if limit and self.nbytes_host > 0.5 * limit:
            return "stream"
        return "resident"

    def packed_bins(self) -> C.ChunkedPackedBins:
        """Page the compressed chunk stack onto the device (cached) as the
        traced representation the training scan consumes. Page-in verifies
        per-chunk crc32s and retries transient failures (DESIGN.md §13)."""
        if self._device_stack is None:
            self._device_stack = self._page_in()
        return C.ChunkedPackedBins(
            packed=self._device_stack,
            bits=self.bits,
            chunk_rows=self.chunk_rows,
            n_rows=self.n_rows,
        )

    def _page_in(self) -> jax.Array:
        """Host -> device transfer with integrity verification and
        retry/backoff. The chunk_load / chunk_corrupt fault sites
        (repro.testing.faults) live here. Verification follows the
        verify_chunks policy: "once" verifies only stacks with unverified
        chunks (first page-in, or after a retry cleared the flags),
        "always" re-verifies every page-in, "never" skips."""

        def attempt():
            FA.check("chunk_load")
            stack = FA.corrupt_array("chunk_corrupt", self._host_packed)
            if self.verify_chunks == "always" or (
                self.verify_chunks == "once" and not self._verified.all()
            ):
                RES.verify_chunk_crcs(
                    stack, self._chunk_crcs,
                    context=f"ExternalDMatrix({self.n_rows}x"
                            f"{self.n_features})",
                )
                self._verified[:] = True
            return jnp.asarray(stack)

        def note(n, exc):
            self._verified[:] = False
            warnings.warn(
                f"chunk page-in failed ({exc}); "
                f"retry {n + 1}/{self.load_retries}"
            )

        return RES.with_retries(
            attempt, retries=self.load_retries, backoff=self.load_backoff,
            retry_on=(OSError, RES.ChunkIntegrityError), on_retry=note,
        )

    def _load_chunk(self, i: int) -> jax.Array:
        """Page ONE chunk host -> device: the per-chunk analogue of
        `_page_in`, with the same fault sites, verify policy and
        retry/backoff. A retry clears the chunk's verified flag so the
        re-attempt re-checks the crc even under the "once" policy."""

        def attempt():
            FA.check("chunk_load")
            chunk = FA.corrupt_array("chunk_corrupt", self._host_packed[i])
            if self.verify_chunks == "always" or (
                self.verify_chunks == "once" and not self._verified[i]
            ):
                RES.verify_chunk_crcs(
                    chunk[None], self._chunk_crcs[i : i + 1],
                    context=f"ExternalDMatrix chunk {i}",
                )
                self._verified[i] = True
            return jnp.asarray(chunk)

        def note(n, exc):
            self._verified[i] = False
            warnings.warn(
                f"chunk {i} page-in failed ({exc}); "
                f"retry {n + 1}/{self.load_retries}"
            )

        return RES.with_retries(
            attempt, retries=self.load_retries, backoff=self.load_backoff,
            retry_on=(OSError, RES.ChunkIntegrityError), on_retry=note,
        )

    def chunk_pager(self, indices=None, prefetch: int | None = None
                    ) -> ChunkPager:
        """A `ChunkPager` over `indices` (default: every chunk in order).

        When the stack is already device-resident the pager serves cached
        slices synchronously (they were verified when paged in); otherwise
        a background worker stages up to `prefetch` chunks (default
        `self.prefetch_chunks`) ahead of the consumer via `_load_chunk`,
        so transfer of chunk k+1 overlaps compute on chunk k. Iterate
        `(index, chunk)` pairs; iteration cleans up the worker on exit."""
        if indices is None:
            indices = range(self.n_chunks)
        if self._device_stack is not None:
            stack = self._device_stack
            return ChunkPager(lambda i: stack[i], indices, 0)
        if prefetch is None:
            prefetch = self.prefetch_chunks
        return ChunkPager(self._load_chunk, indices, prefetch)

    def iter_device_chunks(self):
        """Yield each packed chunk as a device array, ONE at a time — the
        streaming predict path (DESIGN.md §14). Unlike `packed_bins()` the
        full device stack is never materialised: device transients stay
        bounded by the pager ring (prefetch_chunks staged + 1 in use), and
        `nbytes_device` stays 0. Chunk crc32s are verified per the
        verify_chunks policy with the same retry/backoff as training (when
        the stack is already device-resident the cached copy is served
        instead — it was verified when paged in)."""
        for _, chunk in self.chunk_pager():
            yield chunk

    def unload(self) -> None:
        """Drop the device copy of the chunk stack (page out). The host
        stack is retained; the next `packed_bins()` pages back in."""
        self._device_stack = None

    def same_cuts(self, other) -> bool:
        return cuts_equal(self.cuts, getattr(other, "cuts", None))

    def __repr__(self) -> str:  # pragma: no cover - cosmetic
        return (
            f"ExternalDMatrix({self.n_rows}x{self.n_features}, "
            f"{self.n_chunks} chunks of {self.chunk_rows} rows, "
            f"{self.bits}-bit, {self.nbytes_host / 1e6:.2f} MB host"
            f"{', labelled' if self.label is not None else ''})"
        )


def _rechunk(xs: list, chunk_rows: int) -> list:
    """Re-slice a list of arbitrary-sized row chunks into uniform
    chunk_rows pieces (the last may be short) without building the full
    matrix: peak extra memory is one output chunk."""
    out, buf, buffered = [], [], 0
    for chunk in xs:
        buf.append(chunk)
        buffered += chunk.shape[0]
        while buffered >= chunk_rows:
            take, need = [], chunk_rows
            while need > 0:
                head = buf[0]
                if head.shape[0] <= need:
                    take.append(head)
                    need -= head.shape[0]
                    buf.pop(0)
                else:
                    take.append(head[:need])
                    buf[0] = head[need:]
                    need = 0
            out.append(take[0] if len(take) == 1 else np.concatenate(take))
            buffered -= chunk_rows
    if buffered:
        out.append(buf[0] if len(buf) == 1 else np.concatenate(buf))
    return out
