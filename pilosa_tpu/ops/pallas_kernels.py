"""Pallas TPU kernels for the hottest scans, explicitly tiled.

The XLA path (ops.intersection_counts_matrix) already fuses AND+popcount+
reduce; the Pallas versions add explicit tiling so the fragment matrix
streams HBM→VMEM in (TILE_R, TILE_W) blocks with the src row pinned in
VMEM, accumulating per-row partial popcounts across word tiles.

One kernel here is on the served path: ``stacked_block_counts_onepass``,
the per-block popcounts of the one-chip stacked TopN scorer
(ops.packed.sparse_intersection_counts_stacked, launched by
BatchedScorer or traced into a fused program as a TopN head). It runs
where that program is lowered for a TPU and the source stack fits
``ONEPASS_VMEM_BUDGET`` (``onepass_fits``); elsewhere the scorer keeps
its XLA gather, and the batch and mesh scorers never call it. The
others are called only by the tests (ROADMAP D4). Every kernel compiles
for a described v5e (tests/test_tpu_compile.py); ``interpret=True`` runs
them on the CPU so their semantics are tested there.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

TILE_R = 512  # rank-1 i32 outputs tile at T(512) in XLA layout on TPU
TILE_W = 1024  # uint32 words per tile (keeps a 2 MB mat block in VMEM)


def _scores_kernel(src_ref, mat_ref, out_ref):
    # out is (1, R) so it carries the fixed (8, 128) rank-2 layout —
    # rank-1 outputs get size-dependent XLA tilings (T(512)/T(1024)/…)
    # that a fixed Mosaic block size can't match. The (1, TILE_R) block
    # is revisited across the word grid for accumulation.
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        out_ref[:] = jnp.zeros_like(out_ref)

    block = jnp.bitwise_and(mat_ref[:], src_ref[:])  # (TILE_R, TILE_W)
    partial = jnp.sum(
        jax.lax.population_count(block).astype(jnp.int32), axis=1
    )
    out_ref[:] += partial[None, :]


@functools.partial(jax.jit, static_argnames=("interpret",))
def intersection_counts_matrix_pallas(src, mat, *, interpret: bool = False):
    """popcount(src & row) per row: u32[W], u32[R, W] -> i32[R].

    R must be a multiple of TILE_R and W of TILE_W (the executor pads
    the staged matrix; padding rows score 0 and are sliced off by the
    caller).
    """
    r, w = mat.shape
    grid = (r // TILE_R, w // TILE_W)
    out = pl.pallas_call(
        _scores_kernel,
        out_shape=jax.ShapeDtypeStruct((1, r), jnp.int32),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, TILE_W), lambda i, j: (0, j), memory_space=pltpu.VMEM),
            pl.BlockSpec(
                (TILE_R, TILE_W), lambda i, j: (i, j), memory_space=pltpu.VMEM
            ),
        ],
        out_specs=pl.BlockSpec(
            (1, TILE_R), lambda i, j: (0, i), memory_space=pltpu.VMEM
        ),
        interpret=interpret,
    )(src.reshape(1, w), mat)
    return out[0]


def _batched_scores_kernel(q_static, srcs_ref, mat_ref, out_ref):
    # Grid (R/TILE_R, W/TILE_W), j innermost: the (TILE_R, TILE_W) mat
    # block is fetched from HBM once per (i, j) and reused for all Q
    # sources — the whole point of batching. out is (Q, TILE_R), index
    # (i, j) -> (0, i): constant across consecutive j steps, the safe
    # Pallas revisit/accumulate pattern.
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        out_ref[:] = jnp.zeros_like(out_ref)

    mat = mat_ref[:]  # (TILE_R, TILE_W)
    acc = []
    for q in range(q_static):  # static unroll; Q is bucketed small
        block = jnp.bitwise_and(mat, srcs_ref[q, :][None, :])
        acc.append(
            jnp.sum(jax.lax.population_count(block).astype(jnp.int32), axis=1)
        )
    out_ref[:] += jnp.stack(acc, axis=0)  # (Q, TILE_R)


@functools.partial(jax.jit, static_argnames=("interpret",))
def intersection_counts_matrix_batch_pallas(srcs, mat, *, interpret: bool = False):
    """Batched scoring: u32[Q, W], u32[R, W] -> i32[Q, R].

    R must be a multiple of TILE_R and W of TILE_W (see pad_for_pallas).
    Q is static per compilation — callers bucket Q (pad sources with
    zeros; a zero source scores 0 everywhere) to bound recompiles.
    """
    q, w = srcs.shape
    if q > 512:
        # the kernel unrolls the Q loop; beyond ~512 Mosaic compile
        # time explodes — chunk larger batches at the call site
        raise ValueError(f"batch too large for kernel unroll: {q} > 512")
    r, _ = mat.shape
    grid = (r // TILE_R, w // TILE_W)
    return pl.pallas_call(
        functools.partial(_batched_scores_kernel, q),
        out_shape=jax.ShapeDtypeStruct((q, r), jnp.int32),
        grid=grid,
        in_specs=[
            pl.BlockSpec((q, TILE_W), lambda i, j: (0, j), memory_space=pltpu.VMEM),
            pl.BlockSpec(
                (TILE_R, TILE_W), lambda i, j: (i, j), memory_space=pltpu.VMEM
            ),
        ],
        out_specs=pl.BlockSpec(
            (q, TILE_R), lambda i, j: (0, i), memory_space=pltpu.VMEM
        ),
        interpret=interpret,
    )(srcs, mat)


def _groupby_planes_kernel(p_static, planes_ref, groups_ref, out_ref):
    # Grid (K/TILE_R, W/TILE_W), j innermost: each (TILE_R, TILE_W)
    # group block is fetched from HBM once per (i, j) and reused for
    # all P bit planes pinned in VMEM — the segmented-reduce shape of a
    # GroupBy panel (segment = (plane, group) pair). out is (P, TILE_R)
    # at index (0, i): constant across consecutive j steps, the safe
    # revisit/accumulate pattern.
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        out_ref[:] = jnp.zeros_like(out_ref)

    grp = groups_ref[:]  # (TILE_R, TILE_W)
    acc = []
    for p in range(p_static):  # static unroll; P = bit_depth+1 stays small
        block = jnp.bitwise_and(grp, planes_ref[p, :][None, :])
        acc.append(
            jnp.sum(jax.lax.population_count(block).astype(jnp.int32), axis=1)
        )
    out_ref[:] += jnp.stack(acc, axis=0)  # (P, TILE_R)


@functools.partial(jax.jit, static_argnames=("interpret",))
def groupby_plane_counts_pallas(planes, groups, *, interpret: bool = False):
    """Segmented GroupBy×BSI reduction: planes u32[P, W], groups
    u32[K, W] -> i32[P, K].

    K (the panel's cross-product size) is the streaming axis; the few
    bit planes stay resident in VMEM for the whole scan, so each group
    block crosses HBM exactly once regardless of bit depth. K must be a
    multiple of TILE_R and W of TILE_W (pad_for_pallas; zero-padded
    groups score 0 everywhere and are sliced off by the caller). The
    jit fallback is ops.packed.groupby_plane_counts (note the
    transposed [K, P] output there).
    """
    p, w = planes.shape
    if p > 512:
        # the kernel unrolls the plane loop; bit depth is ≤ 64 in
        # practice but guard the Mosaic compile-time cliff anyway
        raise ValueError(f"plane batch too large for kernel unroll: {p} > 512")
    k, _ = groups.shape
    grid = (k // TILE_R, w // TILE_W)
    return pl.pallas_call(
        functools.partial(_groupby_planes_kernel, p),
        out_shape=jax.ShapeDtypeStruct((p, k), jnp.int32),
        grid=grid,
        in_specs=[
            pl.BlockSpec((p, TILE_W), lambda i, j: (0, j), memory_space=pltpu.VMEM),
            pl.BlockSpec(
                (TILE_R, TILE_W), lambda i, j: (i, j), memory_space=pltpu.VMEM
            ),
        ],
        out_specs=pl.BlockSpec(
            (p, TILE_R), lambda i, j: (0, i), memory_space=pltpu.VMEM
        ),
        interpret=interpret,
    )(planes, groups)


def _expand_runs_kernel(starts_ref, ends_ref, out_ref):
    # One (1, TILE_W) word tile per grid step; every run clamps its
    # [start, end] bit interval against each word's 32-bit span and
    # ORs in the overlap mask. Runs are few (RLE containers cap at
    # 2048 intervals) while words are many, so the run loop stays
    # sequential and the word axis rides the VPU lanes. The endpoints
    # are scalar-prefetched into SMEM: the loop reads one scalar per
    # run from the ref (Mosaic has no dynamic slice of a loaded vector).
    i = pl.program_id(0)
    full = jnp.uint32(0xFFFFFFFF)
    wid = i * TILE_W + jax.lax.broadcasted_iota(jnp.int32, (1, TILE_W), 1)
    word_lo = wid * 32
    word_hi = word_lo + 31

    def body(k, acc):
        lo = jnp.maximum(starts_ref[k], word_lo)
        hi = jnp.minimum(ends_ref[k], word_hi)
        sb = jnp.clip(lo - word_lo, 0, 31).astype(jnp.uint32)
        eb = jnp.clip(hi - word_lo, 0, 31).astype(jnp.uint32)
        m = (full << sb) & (full >> (31 - eb))
        return acc | jnp.where(lo <= hi, m, jnp.uint32(0))

    out_ref[:] = jax.lax.fori_loop(
        0, starts_ref.shape[0], body, jnp.zeros((1, TILE_W), jnp.uint32)
    )


@functools.partial(jax.jit, static_argnames=("num_words", "interpret"))
def expand_runs_pallas(run_starts, run_ends, num_words: int, *, interpret: bool = False):
    """On-device roaring RLE expansion: i32[N] inclusive global bit
    endpoints -> packed u32[num_words] (array-container positions ride
    along as width-1 runs). num_words must be a multiple of TILE_W (a
    row is 32768 words, so stacked rows always are); pad the run list
    with start > end — an empty interval contributes nothing. The jit
    scatter form (ops.packed.expand_blocks) is what the stager calls; it
    also covers dense bitmap containers."""
    out = pl.pallas_call(
        _expand_runs_kernel,
        out_shape=jax.ShapeDtypeStruct((1, num_words), jnp.uint32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(num_words // TILE_W,),
            in_specs=[],
            out_specs=pl.BlockSpec(
                (1, TILE_W), lambda i, starts, ends: (0, i), memory_space=pltpu.VMEM
            ),
        ),
        interpret=interpret,
    )(run_starts, run_ends)
    return out[0]


def pad_for_pallas(mat):
    """Pad rows to TILE_R and words to TILE_W multiples."""
    import numpy as np

    r, w = mat.shape
    rp = (-r) % TILE_R
    wp = (-w) % TILE_W
    if rp or wp:
        mat = np.pad(mat, ((0, rp), (0, wp)))
    return mat, r


# -- the one-chip stacked TopN scorer's block popcounts ----------------------

# A 2^16-bit container block as the stager lays it out: u32[16, 128], two
# (8, 128) tiles, so a block is one leading index of a [B, 16, 128] bundle.
BLOCK_SUBLANES = 16
BLOCK_LANES = 128
STACKED_BLOCK_SHAPE = (BLOCK_SUBLANES, BLOCK_LANES)
# blocks streamed a grid step (8 MiB); the coordinate arrays' rank-1 SMEM
# blocks must match XLA's T(1024) tiling of an s32 vector, so never fewer
ONEPASS_TILE = 1024
# VMEM the resident source stack may take: 256 shards of 128 KiB, a
# quarter of v5e's 128 MiB, beside the two streamed tiles
ONEPASS_VMEM_BUDGET = 32 << 20
_ROW_BYTES = 16 * BLOCK_SUBLANES * BLOCK_LANES * 4  # one shard row, 128 KiB


def onepass_fits(n_shards: int) -> bool:
    """Whether a source stack of ``n_shards`` rows stays resident in
    VMEM for the one-pass kernel."""
    return n_shards * _ROW_BYTES <= ONEPASS_VMEM_BUDGET


def stacked_scorer_how(n_shards: int) -> str:
    """How a launch of the one-chip stacked scorer over ``n_shards``
    reads its bundle on the default backend: ``onepass`` (this module's
    kernel) or ``gather`` (XLA's gather, AND and popcount). The rule
    ``ops.packed`` applies when it lowers the scorer, for the host's
    ``topn.scorer_launches`` counter."""
    tpu = jax.default_backend() == "tpu"
    return "onepass" if tpu and onepass_fits(n_shards) else "gather"


def _onepass_kernel(shard_ref, slot_ref, srcs_ref, blocks_ref, out_ref, rows_ref):
    # Grid over tiles of blocks; the source stack [S * 16, 16, 128] is the
    # same block at every step, so it is fetched once and stays. Each
    # block's source is one leading index of it, (shard, slot) read from
    # the tile's SMEM coordinates: AND, popcount and a sum over sublanes
    # make one row of lane partials, 128 rows a group; the group's
    # transpose summed over sublanes is its 128 counts, lane-dense.
    group = BLOCK_LANES

    def body(g, carry):
        base = pl.multiple_of(g * group, group)
        for j in range(group):
            b = base + j
            src = srcs_ref[shard_ref[b] * BLOCK_SUBLANES + slot_ref[b]]
            pc = jax.lax.population_count(jnp.bitwise_and(blocks_ref[b], src))
            rows_ref[j : j + 1, :] = jnp.sum(
                pc.astype(jnp.int32), axis=0, keepdims=True
            )
        out_ref[:, pl.ds(base, group)] = jnp.sum(
            rows_ref[...].T, axis=0, keepdims=True
        )
        return carry

    jax.lax.fori_loop(0, blocks_ref.shape[0] // group, body, 0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def stacked_block_counts_onepass(
    srcs, blocks, block_slot, block_shard, *, interpret: bool = False
):
    """popcount(block & its source block) per block, reading the bundle
    once: srcs u32[S, W], blocks u32[B, 16, 128], block_slot and
    block_shard i32[B] -> i32[B].

    The source stack is held in VMEM for the whole launch (single
    buffered: its block never changes), so S must pass onepass_fits;
    the blocks stream HBM→VMEM in tiles of ONEPASS_TILE, and only the
    counts go back. B that is not a multiple of the tile is padded with
    zero blocks aimed at (shard 0, slot 0), which count 0.
    """
    s = srcs.shape[0]
    b = blocks.shape[0]
    pad = (-b) % ONEPASS_TILE
    if pad:
        blocks = jnp.pad(blocks, ((0, pad), (0, 0), (0, 0)))
        block_slot = jnp.pad(block_slot, (0, pad))
        block_shard = jnp.pad(block_shard, (0, pad))
    n = b + pad
    tile = ONEPASS_TILE
    resident = s * _ROW_BYTES
    streamed = 2 * tile * BLOCK_SUBLANES * BLOCK_LANES * 4
    out = pl.pallas_call(
        _onepass_kernel,
        out_shape=jax.ShapeDtypeStruct((1, n), jnp.int32),
        grid=(n // tile,),
        in_specs=[
            pl.BlockSpec((tile,), lambda i: (i,), memory_space=pltpu.SMEM),
            pl.BlockSpec((tile,), lambda i: (i,), memory_space=pltpu.SMEM),
            pl.BlockSpec(
                (s * BLOCK_SUBLANES, BLOCK_SUBLANES, BLOCK_LANES),
                lambda i: (0, 0, 0),
                memory_space=pltpu.VMEM,
                pipeline_mode=pl.Buffered(1),
            ),
            pl.BlockSpec(
                (tile, BLOCK_SUBLANES, BLOCK_LANES),
                lambda i: (i, 0, 0),
                memory_space=pltpu.VMEM,
            ),
        ],
        out_specs=pl.BlockSpec((1, tile), lambda i: (0, i), memory_space=pltpu.VMEM),
        scratch_shapes=[pltpu.VMEM((BLOCK_LANES, BLOCK_LANES), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=resident + streamed + (8 << 20),
        ),
        interpret=interpret,
        name="stacked_block_counts_onepass",
    )(
        block_shard,
        block_slot,
        srcs.reshape(s * BLOCK_SUBLANES, BLOCK_SUBLANES, BLOCK_LANES),
        blocks,
    )
    return out[0, :b]
