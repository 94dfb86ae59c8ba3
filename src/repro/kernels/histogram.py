"""Pallas TPU kernel: gradient histogram build from the bit-packed matrix.

This is the compute hot spot of the paper (§2.3 BuildPartialHistograms) and
the centrepiece of the CUDA->TPU adaptation (DESIGN.md §3/§4): CUDA builds
histograms with atomicAdd scatter; TPU has no fast atomics, so the scatter
is recast as a dense **one-hot x gradient matmul on the MXU**:

    hist[node, f, bin, :] = sum_rows onehot(node*B + bin)[row] * gh[row, :]
                          = onehot.T @ gh        (contraction over rows)

The quantised matrix arrives *compressed* (paper §2.2): `bits`-wide bin ids
packed into uint32 words, column-major per feature. In the compressed-native
training path (DESIGN.md §2) these are the training matrix's own resident
words, handed over untouched via ops.build_histograms_kernel_packed — no
unpack/repack round trip anywhere between quantisation and this kernel. The
kernel unpacks with VPU shift/mask ops in VMEM — the paper's "runtime
bitwise unpacking", which costs a few vector ops and buys >=4x HBM traffic
reduction on the dominant input stream.

Blocking (defaults; VMEM budget in parentheses for bits=8):
  grid = (node_blocks, feature_blocks, row_blocks)   row axis innermost
  packed block  (F_BLK=8, W_BLK=64)  uint32               (2 KB)
  gh block      (ROWS_BLK=spw*W_BLK=256, 2) f32           (2 KB)
  one-hot       (ROWS_BLK, NODES_BLK*B=2048) f32          (2 MB scratch)
  out block     (NODES_BLK=8, F_BLK, B, 2) f32 accumulator (128 KB)
All matmul dims are multiples of 128 when B=256 (two MXU lane groups) and
ROWS_BLK=256 — MXU-aligned per DESIGN.md §4. Accumulation across row blocks
uses the sequential innermost grid axis (out block revisited, += pattern).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(
    packed_ref,  # (F_BLK, W_BLK) uint32
    gh_ref,  # (ROWS_BLK, 2) f32
    pos_ref,  # (ROWS_BLK, 1) i32
    out_ref,  # (NODES_BLK, F_BLK, B, 2) f32
    *,
    bits: int,
    nodes_blk: int,
    max_bins: int,
):
    nb = pl.program_id(0)
    rb = pl.program_id(2)
    f_blk, w_blk = packed_ref.shape
    spw = 32 // bits
    rows = w_blk * spw
    width = nodes_blk * max_bins

    # --- runtime decompression (paper §2.2) ------------------------------
    words = packed_ref[...]
    shifts = (jnp.arange(spw, dtype=jnp.uint32) * bits)[None, None, :]
    mask = jnp.uint32((1 << bits) - 1)
    bins = ((words[:, :, None] >> shifts) & mask).reshape(f_blk, rows)
    bins = bins.astype(jnp.int32)

    # --- node-block membership -------------------------------------------
    pos = pos_ref[...][:, 0]  # (ROWS_BLK,)
    local = pos - nb * nodes_blk
    valid = (local >= 0) & (local < nodes_blk)
    # invalid rows -> index `width` == off the one-hot range -> zero row.
    base = jnp.where(valid, local * max_bins, width)  # (ROWS_BLK,)
    gh = gh_ref[...]  # (ROWS_BLK, 2)

    # --- one-hot MXU matmul per feature ----------------------------------
    iota = jnp.arange(width, dtype=jnp.int32)[None, :]
    acc = []
    for f in range(f_blk):  # static unroll: F_BLK small
        idx = base + bins[f]  # (ROWS_BLK,)
        onehot = (idx[:, None] == iota).astype(jnp.float32)
        part = jnp.dot(
            onehot.T, gh, preferred_element_type=jnp.float32
        )  # (width, 2)
        acc.append(part.reshape(nodes_blk, max_bins, 2))
    block = jnp.stack(acc, axis=1)  # (NODES_BLK, F_BLK, B, 2)

    @pl.when(rb == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    out_ref[...] += block


def histogram_packed(
    packed: jax.Array,  # (F, W) uint32, W*spw rows (padded)
    gh: jax.Array,  # (N, 2) f32
    positions: jax.Array,  # (N,) i32; value n_nodes = inactive
    n_nodes: int,
    max_bins: int,
    bits: int,
    *,
    nodes_blk: int = 8,
    f_blk: int = 8,
    w_blk: int = 64,
    interpret: bool | None = None,
) -> jax.Array:
    """Returns hist (n_nodes, F, max_bins, 2) f32. Pads rows/features/nodes
    to block multiples internally; dump rows (pos == n_nodes) contribute
    nowhere."""
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    f, w = packed.shape
    n = gh.shape[0]
    spw = 32 // bits
    rows_blk = w_blk * spw

    nodes_blk = min(nodes_blk, max(n_nodes, 1))
    n_nblk = -(-n_nodes // nodes_blk)
    n_fblk = -(-f // f_blk)
    w_pad = (-w) % w_blk
    f_pad = n_fblk * f_blk - f
    n_rows_padded = (w + w_pad) * spw

    packed_p = jnp.pad(packed, ((0, f_pad), (0, w_pad)))
    gh_p = jnp.pad(gh, ((0, n_rows_padded - n), (0, 0)))
    pos_p = jnp.pad(
        positions.astype(jnp.int32), (0, n_rows_padded - n), constant_values=-1
    )[:, None]
    n_rblk = n_rows_padded // rows_blk

    kern = functools.partial(
        _kernel, bits=bits, nodes_blk=nodes_blk, max_bins=max_bins
    )
    out = pl.pallas_call(
        kern,
        grid=(n_nblk, n_fblk, n_rblk),
        in_specs=[
            pl.BlockSpec((f_blk, w_blk), lambda nb, fb, rb: (fb, rb)),
            pl.BlockSpec((rows_blk, 2), lambda nb, fb, rb: (rb, 0)),
            pl.BlockSpec((rows_blk, 1), lambda nb, fb, rb: (rb, 0)),
        ],
        out_specs=pl.BlockSpec(
            (nodes_blk, f_blk, max_bins, 2), lambda nb, fb, rb: (nb, fb, 0, 0)
        ),
        out_shape=jax.ShapeDtypeStruct(
            (n_nblk * nodes_blk, n_fblk * f_blk, max_bins, 2), jnp.float32
        ),
        name="histogram_packed",
        interpret=interpret,
    )(packed_p, gh_p, pos_p)
    return out[:n_nodes, :f]


# --- privatised kernel with explicit DMA pipelining (DESIGN.md §16) ----------
#
# Layout, chosen so that the TPU compiler accepts every block and DMA:
#   * packed words are staged as (F_BLK=8, W_BLK=128) tiles — sublane- and
#     lane-aligned;
#   * (g, h) and positions are staged lane-major, (2, ROWS_BLK) and
#     (1, ROWS_BLK). Within each row chunk they are pre-permuted symbol-slot
#     major (`_slot_major`), so that slot s of a word tile —
#     `(words >> s*bits) & mask`, rows w*spw + s — lines up with the
#     contiguous lane range [s*W_BLK, (s+1)*W_BLK) of the staged (g, h) and
#     positions, and the unpack needs no lane interleave;
#   * the histogram factorises over (node, bin): per feature,
#       hist[(g|h), node, bin] = (onehot(pos) * (g|h)) @ onehot(bin).T
#     a (2*NODES_PAD, W_BLK) x (W_BLK, B) MXU matmul per word slot, with
#     `bin` on the lane axis of the (F_BLK, 2*NODES_PAD, B) accumulator.
#     Rows parked in the dump slot (pos == n_nodes) match no node row.


def _slot_major(v: jax.Array, w_blk: int, spw: int) -> jax.Array:
    """(..., N) row-ordered -> (..., N) with each ROWS_BLK chunk reordered
    from row order (w*spw + s) to slot-major order (s*W_BLK + w)."""
    lead = v.shape[:-1]
    n_chunks = v.shape[-1] // (w_blk * spw)
    v = v.reshape(*lead, n_chunks, w_blk, spw)
    return jnp.swapaxes(v, -1, -2).reshape(*lead, n_chunks * w_blk * spw)


def _private_kernel(
    packed_hbm,  # (F_pad, W_pad) uint32, whole array in HBM/ANY
    gh_hbm,  # (2, N_pad) f32, slot-major within each row chunk
    pos_hbm,  # (1, N_pad) i32, slot-major within each row chunk
    out_ref,  # (1, F_BLK, 2*NODES_PAD, B) f32 — this program's partial
    words_buf,  # VMEM (buffer_depth, F_BLK, W_BLK) uint32 scratch
    gh_buf,  # VMEM (buffer_depth, 2, ROWS_BLK) f32 scratch
    pos_buf,  # VMEM (buffer_depth, 1, ROWS_BLK) i32 scratch
    sem,  # DMA semaphores (3, buffer_depth)
    *,
    bits: int,
    max_bins: int,
    nodes_pad: int,
    f_blk: int,
    w_blk: int,
    chunks_per_private: int,
    buffer_depth: int,
):
    pid = pl.program_id(0)  # which private row group
    fb = pl.program_id(1)  # which feature block
    spw = 32 // bits
    rows_blk = w_blk * spw

    def copies(chunk, slot):
        """The three DMAs that stage row-chunk `chunk` into buffer `slot`."""
        word0 = (pid * chunks_per_private + chunk) * w_blk
        row0 = (pid * chunks_per_private + chunk) * rows_blk
        return (
            pltpu.make_async_copy(
                packed_hbm.at[pl.ds(fb * f_blk, f_blk), pl.ds(word0, w_blk)],
                words_buf.at[slot],
                sem.at[0, slot],
            ),
            pltpu.make_async_copy(
                gh_hbm.at[:, pl.ds(row0, rows_blk)], gh_buf.at[slot],
                sem.at[1, slot],
            ),
            pltpu.make_async_copy(
                pos_hbm.at[:, pl.ds(row0, rows_blk)], pos_buf.at[slot],
                sem.at[2, slot],
            ),
        )

    def start(chunk, slot):
        for c in copies(chunk, slot):
            c.start()

    def wait(chunk, slot):
        for c in copies(chunk, slot):
            c.wait()

    out_ref[...] = jnp.zeros_like(out_ref)
    start(0, 0)

    mask = jnp.uint32((1 << bits) - 1)
    node_iota = jax.lax.broadcasted_iota(jnp.int32, (nodes_pad, w_blk), 0)
    bin_iota = jax.lax.broadcasted_iota(jnp.int32, (max_bins, w_blk), 0)

    def body(chunk, carry):
        slot = chunk % buffer_depth

        # Prefetch the next chunk into the next slot before blocking on this
        # one — with buffer_depth >= 2 the DMA overlaps this chunk's compute.
        if buffer_depth > 1:

            @pl.when(chunk + 1 < chunks_per_private)
            def _prefetch():
                start(chunk + 1, (chunk + 1) % buffer_depth)

        wait(chunk, slot)

        # Per word slot s, the (node one-hot * g | h) left operand, shared
        # by every feature. Lane slices are taken from the refs: Mosaic
        # refuses to broadcast a row lane-sliced out of a loaded value.
        lhs = []
        for s in range(spw):
            lanes = pl.ds(s * w_blk, w_blk)
            node_hot = (node_iota == pos_buf[slot, :, lanes]).astype(
                jnp.float32
            )
            lhs.append(jnp.concatenate(
                [node_hot * gh_buf[slot, 0:1, lanes],
                 node_hot * gh_buf[slot, 1:2, lanes]], axis=0
            ))  # (2*NODES_PAD, W_BLK)

        def feature(fi, c):
            words = words_buf[slot, pl.ds(fi, 1), :]  # (1, W_BLK)
            acc = out_ref[0, fi]
            for s in range(spw):
                bins = ((words >> jnp.uint32(s * bits)) & mask).astype(
                    jnp.int32
                )
                bin_hot = (bin_iota == bins).astype(jnp.float32)
                acc += jax.lax.dot_general(
                    lhs[s], bin_hot, (((1,), (1,)), ((), ())),
                    precision=jax.lax.Precision.HIGHEST,
                    preferred_element_type=jnp.float32,
                )  # (2*NODES_PAD, B)
            out_ref[0, fi] = acc
            return c

        jax.lax.fori_loop(0, f_blk, feature, jnp.int32(0))

        # Single-buffer pipeline: the slot is free only now.
        if buffer_depth == 1:

            @pl.when(chunk + 1 < chunks_per_private)
            def _next():
                start(chunk + 1, 0)

        return carry

    jax.lax.fori_loop(0, chunks_per_private, body, jnp.int32(0))


def _tree_add(parts: jax.Array) -> jax.Array:
    """Merge per-group partial histograms with a binary tree of adds.

    Log-depth, pairwise — the epilogue the paper runs after per-block
    shared-memory histograms are flushed. The summation order is fixed by
    the (static) number of groups, so results are deterministic run-to-run.
    """
    while parts.shape[0] > 1:
        half = parts.shape[0] // 2
        even = parts[0 : 2 * half : 2] + parts[1 : 2 * half : 2]
        if parts.shape[0] % 2:
            even = jnp.concatenate([even, parts[-1:]], axis=0)
        parts = even
    return parts[0]


def build_histograms_packed_kernel(
    packed: jax.Array,  # (F, W) uint32, W*spw rows (padded)
    gh: jax.Array,  # (N, 2) f32
    positions: jax.Array,  # (N,) i32; value n_nodes = inactive
    n_nodes: int,
    max_bins: int,
    bits: int,
    *,
    f_blk: int = 8,
    w_blk: int = 128,
    n_private: int = 8,
    buffer_depth: int = 2,
    interpret: bool | None = None,
) -> jax.Array:
    """Privatised packed-histogram kernel: grid (row_groups, feature_blocks).

    The CUDA kernel's shared-memory privatisation (paper §2.3) mapped to
    TPU: each of `n_private` row groups accumulates its own
    (F_BLK, 2*NODES_PAD, B) histogram in its VMEM output block — never
    contending with other groups — while packed words, (g, h) pairs and
    positions are staged HBM->VMEM with explicit `make_async_copy` DMAs,
    `buffer_depth` chunks in flight (1 = serial, 2 = classic double
    buffering, 4 = deeper pipeline). The per-group partials are merged by a
    log-depth tree-add epilogue (`_tree_add`), the analogue of the CUDA
    grid-wide flush. On the TPU `w_blk` must be a multiple of 128.

    VMEM: the output block is f_blk * 2 * NODES_PAD * max_bins * 4 bytes
    (0.5 MB at n_nodes 32, 256 bins), double-buffered, plus one
    (max_bins, W_BLK) bin one-hot per feature.

    Returns hist (n_nodes, F, max_bins, 2) f32.
    """
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    f, w = packed.shape
    n = gh.shape[0]
    spw = 32 // bits
    rows_blk = w_blk * spw
    nodes_pad = max(8, -(-n_nodes // 8) * 8)

    n_fblk = -(-f // f_blk)
    f_pad = n_fblk * f_blk - f
    chunks_per_private = max(1, -(-w // (n_private * w_blk)))
    w_padded = n_private * chunks_per_private * w_blk
    n_rows_padded = w_padded * spw

    packed_p = jnp.pad(packed, ((0, f_pad), (0, w_padded - w)))
    gh_p = _slot_major(
        jnp.pad(gh, ((0, n_rows_padded - n), (0, 0))).T, w_blk, spw
    )
    # Padding rows -> dump slot n_nodes, like inactive rows; clamp real
    # inactive markers the same way.
    pos_p = _slot_major(
        jnp.pad(
            jnp.minimum(positions, n_nodes).astype(jnp.int32),
            (0, n_rows_padded - n),
            constant_values=n_nodes,
        )[None, :],
        w_blk, spw,
    )

    kern = functools.partial(
        _private_kernel,
        bits=bits,
        max_bins=max_bins,
        nodes_pad=nodes_pad,
        f_blk=f_blk,
        w_blk=w_blk,
        chunks_per_private=chunks_per_private,
        buffer_depth=buffer_depth,
    )
    partials = pl.pallas_call(
        kern,
        grid=(n_private, n_fblk),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec(
            (1, f_blk, 2 * nodes_pad, max_bins),
            lambda pid, fb: (pid, fb, 0, 0),
        ),
        out_shape=jax.ShapeDtypeStruct(
            (n_private, n_fblk * f_blk, 2 * nodes_pad, max_bins), jnp.float32
        ),
        scratch_shapes=[
            pltpu.VMEM((buffer_depth, f_blk, w_blk), jnp.uint32),
            pltpu.VMEM((buffer_depth, 2, rows_blk), jnp.float32),
            pltpu.VMEM((buffer_depth, 1, rows_blk), jnp.int32),
            pltpu.SemaphoreType.DMA((3, buffer_depth)),
        ],
        name="histogram_private",
        interpret=interpret,
    )(packed_p, gh_p, pos_p)
    merged = _tree_add(partials)  # (F_pad, 2*NODES_PAD, B)
    hist = merged.reshape(n_fblk * f_blk, 2, nodes_pad, max_bins)
    return hist.transpose(2, 0, 3, 1)[:n_nodes, :f]
