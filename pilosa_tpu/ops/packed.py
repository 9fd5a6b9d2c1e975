"""Packed-word bitmap kernels — the TPU data plane (L0 compute).

A fragment row (2^20 columns, reference fragment.go:47-48) is staged in
device memory as 32,768 packed ``uint32`` words (TPUs have no native
64-bit integers; the CPU engine's uint64 words reinterpret losslessly as
little-endian uint32 pairs). The reference's per-container Go loops
(reference roaring/roaring.go:1836-2449) become word-wise vector ops +
``lax.population_count`` here: on TPU the VPU processes 8x128 lanes of
these per cycle and XLA fuses whole Intersect/Union chains into a single
HBM pass.

All kernels keep shapes static (row width fixed per shard) and treat row
*values* — including range predicates — as traced arguments, so a query
stream with varying rows/predicates never recompiles.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from pilosa_tpu.ops import pallas_kernels

# Words per shard-row on device: 2^20 bits / 32.
SHARD_WIDTH = 1 << 20
WORDS_PER_ROW = SHARD_WIDTH // 32
# Words per 2^16-bit container block: the sparse-staging granule.
CONTAINER_WORDS = (1 << 16) // 32
CONTAINERS_PER_ROW = SHARD_WIDTH >> 16  # 16


def u64_to_u32(words64: np.ndarray) -> np.ndarray:
    """Reinterpret uint64 packed words as uint32 device words (little-endian:
    bit p of the row lands in u32 word p>>5, bit p&31)."""
    return words64.view("<u8").view("<u4")


def u32_to_u64(words32: np.ndarray) -> np.ndarray:
    return words32.view("<u4").view("<u8")


# -- elementwise boolean algebra --------------------------------------------
# Tiny named wrappers so lowered call trees read like the PQL ops they
# implement (reference executor.go:704-1000). XLA fuses chains of these.


def and_(a, b):
    return jnp.bitwise_and(a, b)


def or_(a, b):
    return jnp.bitwise_or(a, b)


def xor_(a, b):
    return jnp.bitwise_xor(a, b)


def andnot(a, b):
    """a AND NOT b — the Difference op."""
    return jnp.bitwise_and(a, jnp.bitwise_not(b))


def not_(a):
    return jnp.bitwise_not(a)


# -- popcount ----------------------------------------------------------------


@jax.jit
def count_bits(words) -> jax.Array:
    """Total set bits in a packed word array (any shape) -> int32 scalar."""
    pc = jax.lax.population_count(words)
    return jnp.sum(pc.astype(jnp.int32))


@jax.jit
def count_bits_rows(mat) -> jax.Array:
    """Per-row popcount: u32[R, W] -> i32[R]."""
    pc = jax.lax.population_count(mat)
    return jnp.sum(pc.astype(jnp.int32), axis=-1)


@jax.jit
def intersection_count(a, b) -> jax.Array:
    """popcount(a & b) without materialising the intersection
    (reference roaring.go:344 IntersectionCount)."""
    return count_bits(jnp.bitwise_and(a, b))


@jax.jit
@jax.named_scope("topn_score_dense")
def intersection_counts_matrix(src, mat) -> jax.Array:
    """TopN scoring kernel: popcount(src & row) for every row.

    src: u32[W]; mat: u32[R, W] -> i32[R]. One HBM pass over the
    fragment matrix; replaces the reference's per-candidate
    ``Src.IntersectionCount(f.row(id))`` heap loop (fragment.go:985).
    """
    pc = jax.lax.population_count(jnp.bitwise_and(mat, src[None, :]))
    return jnp.sum(pc.astype(jnp.int32), axis=-1)


@functools.partial(jax.jit, static_argnames=("num_rows",))
@jax.named_scope("topn_score_sparse")
def sparse_intersection_counts(src, blocks, block_row, block_slot, num_rows: int):
    """TopN scoring over block-sparse candidate rows.

    Dense staging materialises every candidate row at 128 KB regardless
    of sparsity (SURVEY.md §7 hard part 2); at the 1B-row scale most of
    those words are zero. Here only nonempty 2^16-bit container blocks
    are staged: ``blocks`` u32[B, 2048] with coordinate arrays
    ``block_row`` i32[B] (candidate index) and ``block_slot`` i32[B]
    (which of the row's 16 container positions). The kernel gathers the
    matching src block, popcounts the AND, and segment-sums per row —
    bit-identical to the dense matrix pass because absent blocks
    contribute zero to an intersection.

    src: u32[W]; returns i32[num_rows] (num_rows static — callers pad
    candidate counts to powers of two to bound recompiles).
    """
    src_blk = src.reshape(-1, CONTAINER_WORDS)[block_slot]
    pc = jax.lax.population_count(jnp.bitwise_and(blocks, src_blk))
    per_block = jnp.sum(pc.astype(jnp.int32), axis=-1)
    return jax.ops.segment_sum(per_block, block_row, num_segments=num_rows)


def stacked_block_counts_gather(srcs, blocks, block_slot, block_shard):
    """popcount(block & its source block) per block, in XLA: the source
    blocks gathered into a [B, 16, 128] temporary, ANDed, popcounted
    and summed. srcs u32[S, W]; blocks u32[B, 16, 128] -> i32[B]."""
    per_shard = srcs.reshape(srcs.shape[0], CONTAINERS_PER_ROW, *blocks.shape[1:])
    src_blk = per_shard[block_shard, block_slot]
    pc = jax.lax.population_count(jnp.bitwise_and(blocks, src_blk))
    return jnp.sum(pc.astype(jnp.int32), axis=(1, 2))


def stacked_block_counts(srcs, blocks, block_slot, block_shard):
    """Per-block popcounts of the stacked scorer. Lowered for a TPU with
    a source stack that fits its VMEM budget, one Pallas kernel reads
    the bundle once (pallas_kernels.stacked_block_counts_onepass);
    otherwise, and on every other platform, XLA's gather
    (stacked_block_counts_gather), which writes the gathered sources
    and reads them back. pallas_kernels.stacked_scorer_how states the
    same rule for the host."""
    if not pallas_kernels.onepass_fits(srcs.shape[0]):
        return stacked_block_counts_gather(srcs, blocks, block_slot, block_shard)
    return jax.lax.platform_dependent(
        srcs,
        blocks,
        block_slot,
        block_shard,
        tpu=pallas_kernels.stacked_block_counts_onepass,
        default=stacked_block_counts_gather,
    )


@functools.partial(jax.jit, static_argnames=("num_rows",))
@jax.named_scope("topn_score_stacked")
def sparse_intersection_counts_stacked(
    srcs, blocks, block_row, block_slot, block_shard, num_rows: int
):
    """Cross-shard TopN scoring in ONE dispatch.

    Per-shard sequential kernel launches round-trip the host once per
    shard. Here every
    shard's candidate blocks are concatenated (block_shard says which
    shard a block belongs to, block_row is a GLOBAL segment id =
    shard_index * chunk + local candidate index) and one program of
    block popcounts (stacked_block_counts) + segment-sum serves the
    whole index — the single-device analog of the reference's per-node
    scatter-gather collapsing into one program (reference
    executor.go:1444-1593).

    srcs: u32[S, W]; blocks: u32[B, 16, 128] (a 2048-word container
    block as two (8, 128) tiles); returns i32[num_rows].
    """
    per_block = stacked_block_counts(srcs, blocks, block_slot, block_shard)
    return jax.ops.segment_sum(per_block, block_row, num_segments=num_rows)


@functools.partial(
    jax.jit, static_argnames=("num_rows", "n_shards", "chunk")
)
def sparse_intersection_counts_stacked_mat(
    srcs,
    blocks,
    block_row,
    block_slot,
    block_shard,
    num_rows: int,
    n_shards: int,
    chunk: int,
):
    """Matrix form of the stacked cross-shard scorer: i32[n_shards,
    chunk] trimmed and reshaped ON DEVICE, so a caller (the fused
    whole-query program) transfers exactly the per-shard score head —
    never the flat padded vector the host would otherwise slice after
    fetching. num_rows/n_shards/chunk are static; the stacked staging
    keeps num_rows == n_shards * chunk exact, so the slice is a
    shape-level guarantee, not a copy."""
    flat = sparse_intersection_counts_stacked(
        srcs, blocks, block_row, block_slot, block_shard, num_rows
    )
    return flat[: n_shards * chunk].reshape(n_shards, chunk)


_BATCH_GROUP = 8  # queries scored per block-stream pass (footprint knob)


@functools.partial(jax.jit, static_argnames=("num_rows",))
@jax.named_scope("topn_score_stacked")
def sparse_intersection_counts_stacked_batch(
    srcs_q, blocks, block_row, block_slot, block_shard, num_rows: int
):
    """Concurrent-query batch of the stacked cross-shard scoring: the
    staged candidate blocks stream from HBM once per GROUP of query
    sources (the serving-throughput lever at the 1B-row scale, where
    the block set is hundreds of MB and each extra query would
    otherwise re-read it). A pure lax.map over queries re-read the
    block set per query — measured 147 ms vs 75 ms at Q=32 on the
    1B/64-shard config; vectorizing groups of 8 inside the map keeps
    the peak gather footprint bounded while amortizing the stream.

    The gather stays here: no cell sends concurrent TopNs over one
    bundle, so the one-pass kernel serves the single form alone.

    srcs_q: u32[Q, S, W]; blocks: u32[B, 16, 128]; returns i32[Q, num_rows].
    """
    q = srcs_q.shape[0]
    group = min(_BATCH_GROUP, q)
    if q % group:
        # q is pow2-padded by the batcher; any stray remainder falls
        # back to the per-query sweep rather than a mid-shape compile
        return jax.lax.map(
            lambda s: jax.ops.segment_sum(
                stacked_block_counts_gather(s, blocks, block_slot, block_shard),
                block_row,
                num_segments=num_rows,
            ),
            srcs_q,
        )
    per_shard = srcs_q.reshape(
        q, srcs_q.shape[1], CONTAINERS_PER_ROW, *blocks.shape[1:]
    )

    def one_group(g):
        src_blk = g[:, block_shard, block_slot]  # [G, B, 16, 128]
        pc = jax.lax.population_count(jnp.bitwise_and(blocks[None], src_blk))
        per_block = jnp.sum(pc.astype(jnp.int32), axis=(2, 3))  # [G, B]
        return jax.vmap(
            lambda pb: jax.ops.segment_sum(pb, block_row, num_segments=num_rows)
        )(per_block)

    gs = per_shard.reshape(q // group, group, *per_shard.shape[1:])
    return jax.lax.map(one_group, gs).reshape(q, num_rows)


@functools.partial(jax.jit, static_argnames=("num_rows",))
def sparse_intersection_counts_stacked_batch_list(
    srcs, blocks, block_row, block_slot, block_shard, num_rows: int
):
    """List-of-sources form: stacks inside the jit so a coalesced batch
    costs ONE dispatch instead of stack + kernel.
    srcs: [u32[S, W]] * Q (Q static via the arg structure)."""
    return sparse_intersection_counts_stacked_batch(
        jnp.stack(srcs), blocks, block_row, block_slot, block_shard, num_rows
    )


@jax.jit
@jax.named_scope("topn_score_dense")
def intersection_counts_matrix_batch(srcs, mat) -> jax.Array:
    """Batched TopN scoring: popcount(src_q & row_r) for every (q, r).

    srcs: u32[Q, W]; mat: u32[R, W] -> i32[Q, R]. One logical pass over
    the fragment matrix serves all Q query sources — the concurrent-
    query analog of intersection_counts_matrix (a server batches
    concurrent TopN sources the way a TPU inference server batches
    requests). lax.map keeps the peak footprint at one (R, W) popcount
    buffer instead of the (Q, R, W) a vmap would materialize. An
    explicitly tiled Pallas form exists (ops.pallas_kernels); nothing on
    the served path calls it.
    """
    return jax.lax.map(lambda s: intersection_counts_matrix(s, mat), srcs)


@jax.jit
def intersection_counts_matrix_batch_list(srcs, mat) -> jax.Array:
    """List-of-sources form of the dense batch scorer: stacks inside
    the jit so a coalesced batch costs one dispatch (see
    sparse_intersection_counts_stacked_batch_list)."""
    return intersection_counts_matrix_batch(jnp.stack(srcs), mat)


# -- GroupBy segmented reductions (device-resident analytics) ----------------
#
# A dashboard GroupBy panel is the cross product of its dimensions' row
# bitmaps. Instead of K = ΠR_d point queries (K launches, K plan-cache
# probes, K transports), the per-dimension row stacks are staged once
# and ONE fused program materialises the K group bitmaps in HBM and
# segment-reduces them: popcount per group for Count aggregates, per
# (group, plane) intersection popcounts for Sum aggregates. Group order
# is product order (first dimension slowest), so the host maps counts
# back to row-id tuples by pure arithmetic. The [K, Wf] group transient
# never leaves HBM — callers charge it to the HBM admission governor.


@jax.jit
def combine_groups(dims, filt):
    """Cross-product AND of per-dimension row stacks.

    dims: tuple of u32[R_d, Wf] (rows of one dimension, words flattened
    across the shard batch); filt: u32[Wf] or None, ANDed into every
    group. Returns u32[ΠR_d, Wf] in product order.
    """
    acc = dims[0]
    if filt is not None:
        acc = jnp.bitwise_and(acc, filt[None, :])
    for d in dims[1:]:
        acc = jnp.bitwise_and(acc[:, None, :], d[None, :, :])
        acc = acc.reshape(-1, acc.shape[-1])
    return acc


@jax.jit
@jax.named_scope("groupby_counts")
def groupby_counts(dims, filt):
    """Count-aggregate GroupBy: per-group popcounts i32[ΠR_d] in one
    dispatch (cross product + segmented popcount fused by XLA)."""
    return count_bits_rows(combine_groups(dims, filt))


@jax.jit
def groupby_plane_counts(groups, planes):
    """Sum-aggregate inner reduction: groups u32[K, Wf] × planes
    u32[P, Wf] → i32[K, P] per-(group, plane) intersection popcounts.
    lax.map over the few planes bounds the transient to one [K, Wf]
    popcount buffer (the group matrix is the big axis). An explicitly
    tiled Pallas form of the same reduction exists
    (ops.pallas_kernels.groupby_plane_counts_pallas); nothing on the
    served path calls it."""
    res = jax.lax.map(
        lambda p: jnp.sum(
            jax.lax.population_count(jnp.bitwise_and(groups, p[None, :])).astype(
                jnp.int32
            ),
            axis=-1,
        ),
        planes,
    )
    return res.T


@jax.jit
@jax.named_scope("groupby_sum")
def groupby_sum_reduce(dims, filt, planes):
    """Fused Sum-aggregate GroupBy: one dispatch yielding
    (counts i32[K], plane_counts i32[K, P]). counts[k] is the group's
    column count; plane_counts[k, i] feeds the host's arbitrary-
    precision Σ counts<<i sum assembly (plane P-1 is the not-null row,
    giving the group's non-null value count)."""
    groups = combine_groups(dims, filt)
    return count_bits_rows(groups), groupby_plane_counts(groups, planes)


# -- fold a stack of rows with one op ---------------------------------------


@functools.partial(jax.jit, static_argnames=("op",))
def fold_rows(mat, op: str) -> jax.Array:
    """Reduce u32[K, W] along axis 0 with a boolean op.

    Used for Intersect/Union/Xor over K child rows in one fused pass
    (reference executeIntersectShard chains pairwise; a tree reduce is
    equivalent for these associative ops and vectorises better).
    """
    if op == "and":
        return jax.lax.reduce(mat, jnp.uint32(0xFFFFFFFF), jnp.bitwise_and, (0,))
    if op == "or":
        return jax.lax.reduce(mat, jnp.uint32(0), jnp.bitwise_or, (0,))
    if op == "xor":
        return jax.lax.reduce(mat, jnp.uint32(0), jnp.bitwise_xor, (0,))
    raise ValueError(f"unknown fold op: {op}")


@jax.jit
def count_and_fold(mat) -> jax.Array:
    """popcount(AND-fold of rows) — the Count(Intersect(...)) fast path."""
    return count_bits(fold_rows(mat, "and"))


def device_put_rows(words64_rows: np.ndarray, device=None) -> jax.Array:
    """Stage host uint64-packed rows [R, W64] as device u32[R, 2*W64]."""
    r = words64_rows.shape[0] if words64_rows.ndim == 2 else 1
    w32 = words64_rows.reshape(r, -1).view("<u4")
    return jax.device_put(w32, device)


# -- on-device roaring expansion (tiered staging, ISSUE 17) ------------------
#
# Cold blocks cross PCIe at roaring size instead of packed-word size:
# the host uploads the raw container coordinates (array positions, RLE
# run endpoints, dense bitmap words) and ONE fused scatter program
# expands them to packed u32 words on device. Coordinates are global
# bit offsets into the output (row_index * SHARD_WIDTH + slot * 2^16 +
# local), so one dispatch serves a whole stacked block. All
# contributions are bitwise-disjoint (containers own disjoint word
# ranges; positions/runs within a container are unique/disjoint), so
# scatter-add IS bitwise-or — exact, and add lowers to the cheap
# combiner everywhere. Padding convention (ops/delta.py pad_updates):
# positions pad with 0xFFFFFFFF and word indexes pad with num_words,
# both of which land out of bounds and drop under mode="drop".

_FULL32 = 0xFFFFFFFF


@functools.partial(jax.jit, static_argnames=("num_words",))
def expand_blocks(positions, run_starts, run_ends, dense, dense_word, num_words: int):
    """Expand compressed roaring buffers to packed words on device.

    positions: u32[P] global bit offsets of array-container bits (pad
    0xFFFFFFFF); run_starts/run_ends: u32[N] inclusive global bit
    endpoints of RLE runs (pad with starts > ends); dense: u32[D, 2048]
    raw bitmap-container words with dense_word: i32[D] global word
    offsets (pad num_words). Returns u32[num_words]; callers reshape to
    (rows, WORDS_PER_ROW). num_words must stay below 2^27 so the
    0xFFFFFFFF position pad is out of bounds after >> 5 (67M words for
    the 2047-row i32 coordinate guard — callers clamp).
    """
    words = jnp.zeros((num_words,), jnp.uint32)
    # array containers: one bit per position
    widx = (positions >> 5).astype(jnp.int32)
    mask = jnp.uint32(1) << (positions & 31)
    words = words.at[widx].add(mask, mode="drop")
    # RLE runs, decomposed: partial head/tail word masks scattered by
    # index, full interior words via a +1/-1 diff array + cumsum
    valid = run_starts <= run_ends
    ws = (run_starts >> 5).astype(jnp.int32)
    we = (run_ends >> 5).astype(jnp.int32)
    sbit = run_starts & 31
    ebit = run_ends & 31
    same = ws == we
    head = jnp.uint32(_FULL32) << sbit
    tail = jnp.uint32(_FULL32) >> (31 - ebit)
    oob = jnp.int32(num_words)
    words = words.at[jnp.where(valid, ws, oob)].add(
        head & jnp.where(same, tail, jnp.uint32(_FULL32)), mode="drop"
    )
    words = words.at[jnp.where(valid & ~same, we, oob)].add(tail, mode="drop")
    interior = valid & (we > ws + 1)
    diff = jnp.zeros((num_words + 1,), jnp.int32)
    pad = jnp.int32(num_words + 1)
    diff = diff.at[jnp.where(interior, ws + 1, pad)].add(1, mode="drop")
    diff = diff.at[jnp.where(interior, we, pad)].add(-1, mode="drop")
    cover = jnp.cumsum(diff)[:num_words] > 0
    words = words | jnp.where(cover, jnp.uint32(_FULL32), jnp.uint32(0))
    # dense bitmap containers: raw word blocks at their word offsets
    didx = dense_word[:, None] + jnp.arange(dense.shape[1], dtype=jnp.int32)[None, :]
    return words.at[didx].add(dense, mode="drop")


# -- dispatch-engine support ------------------------------------------------


@functools.partial(jax.jit, donate_argnums=0)
def _zeros_like_donated(buf) -> jax.Array:
    return jnp.zeros_like(buf)


def zeros_like_donated(buf) -> jax.Array:
    """Re-zero a reusable device scratch buffer, donating the old one.

    On TPU/GPU the donated input aliases the output, so a drained
    scratch (e.g. the batcher's pow2 pad lanes) is recycled in place
    instead of allocating fresh HBM every wave. CPU ignores donation
    (and warns), so fall back to a plain zeros_like there.
    """
    db = getattr(buf, "devices", None)
    platform = ""
    try:
        if db is not None:
            platform = next(iter(buf.devices())).platform
    except BaseException:
        platform = ""
    if platform in ("", "cpu"):
        return jnp.zeros_like(buf)
    return _zeros_like_donated(buf)


def materialize_all(arrays: list) -> list:
    """np.asarray over a heterogeneous list of device results.

    One fetch loop for a dispatch wave's outputs: each asarray blocks
    until that computation is done, so later items' device work
    overlaps earlier items' transfers.
    """
    return [np.asarray(a) for a in arrays]
