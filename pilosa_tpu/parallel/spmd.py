"""SPMD query execution over a device mesh (L5 compute plane).

The reference distributes per-shard work with one goroutine per shard
and HTTP scatter-gather between nodes (reference executor.go:1444-1593,
http/client.go). On TPU the same distribution is a *sharding*: fragments
stack into ``uint32[shards, rows, words]`` laid out over a 1-D mesh
axis ``"shards"`` and the cross-shard reduce runs as XLA collectives
inside the compiled program — ``psum`` over ICI for Count/Sum (the
reference's uint64-sum reduceFn), ``all_gather`` for TopN candidate
sets (the reference's Pairs.Add merge) — instead of HTTP fan-out.

The only parallel axis of a bitmap index is the shard (column) axis:
SURVEY.md §2.5 — data parallelism = shard partitioning; rows are never
split. Tensor/pipeline parallelism have no analog here; the mesh is 1-D
by design, scaling to multi-host by making the "shards" axis span hosts
(DCN hops ride the same collectives).
"""

from __future__ import annotations


import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

SHARD_AXIS = "shards"


def make_mesh(devices=None) -> Mesh:
    """1-D mesh over the shard axis."""
    if devices is None:
        devices = jax.devices()
    return Mesh(np.array(devices), (SHARD_AXIS,))


def shard_spec() -> P:
    return P(SHARD_AXIS)


def mesh_is_multiprocess(mesh: Mesh) -> bool:
    """True when the mesh places shards on devices owned by another
    process (a jax.distributed global mesh)."""
    me = jax.process_index()
    return any(d.process_index != me for d in mesh.devices.flat)


def put_sharded(mesh: Mesh, arr: np.ndarray):
    """Place a [S, ...] host array with the leading dim split over the
    mesh — the HBM staging step for a shard batch.

    On a multi-process (jax.distributed) mesh, ``device_put`` cannot
    target non-addressable devices; every process holds the identical
    full host array (the gang replays the same staging on every rank),
    so each process contributes its addressable slices via
    ``make_array_from_callback`` and the result is one global array."""
    sharding = NamedSharding(mesh, P(SHARD_AXIS))
    if mesh_is_multiprocess(mesh):
        return jax.make_array_from_callback(
            arr.shape, sharding, lambda idx: arr[idx]
        )
    return jax.device_put(arr, sharding)


# -- SPMD kernels ------------------------------------------------------------
# Each takes shard-major stacked operands. Written against shard_map so
# the collective structure is explicit (psum/all_gather over ICI).


def count_fold_spmd(mesh: Mesh):
    """Count(Intersect(rows...)) over all shards in one program.

    stacked: u32[S, K, W] (K child rows per shard) -> i32 global count.
    AND-fold + popcount locally, then psum over the shard axis — the
    reference's executeCount sum-reduce (executor.go:966-996) as an ICI
    collective.
    """

    @jax.named_scope("count_fold")
    def kernel(block):  # block: u32[s_local, K, W] per device
        folded = jax.lax.reduce(
            block, jnp.uint32(0xFFFFFFFF), jnp.bitwise_and, (1,)
        )  # [s_local, W]
        local = jnp.sum(jax.lax.population_count(folded).astype(jnp.int32))
        return jax.lax.psum(local, SHARD_AXIS)

    return jax.jit(
        jax.shard_map(
            kernel,
            mesh=mesh,
            in_specs=(P(SHARD_AXIS),),
            out_specs=P(),
        )
    )


def topn_spmd(mesh: Mesh, k: int):
    """TopN candidate generation over all shards in one program.

    src: u32[S, W]; mat: u32[S, R, W] -> (ids i32[S*k], counts i32[S*k])
    on every device: per-shard intersection scores + local top-k, then
    all_gather of the candidate sets — the reference's two-pass TopN
    candidate exchange (executor.go:521-561) riding ICI instead of HTTP.
    The host performs the exact re-score pass (pass 2) as the reference
    does.
    """

    @jax.named_scope("topn")
    def kernel(src, mat):
        # per-device: src u32[s_local, W], mat u32[s_local, R, W]
        scores = jnp.sum(
            jax.lax.population_count(
                jnp.bitwise_and(mat, src[:, None, :])
            ).astype(jnp.int32),
            axis=-1,
        )  # [s_local, R]
        counts, ids = jax.lax.top_k(scores, k)  # [s_local, k] each
        counts = jax.lax.all_gather(counts.reshape(-1), SHARD_AXIS, tiled=True)
        ids = jax.lax.all_gather(ids.reshape(-1), SHARD_AXIS, tiled=True)
        return ids, counts

    return jax.jit(
        jax.shard_map(
            kernel,
            mesh=mesh,
            in_specs=(P(SHARD_AXIS), P(SHARD_AXIS)),
            out_specs=(P(), P()),
            # all_gather's replicated output can't be statically inferred
            # by the varying-manual-axes checker; results are replicated
            # by construction.
            check_vma=False,
        )
    )


def topn_batch_spmd(mesh: Mesh, k: int):
    """Batched TopN candidate generation: Q concurrent query sources
    scored against every shard in one program (the SPMD form of
    executor/batcher.py's continuous micro-batching — the shard matrix
    streams from HBM once per batch, per device).

    srcs: u32[Q, W] (replicated); mat: u32[S, R, W] (shard-sharded)
    -> (ids i32[Q, S*k], counts i32[Q, S*k]) replicated on every device.
    """

    @jax.named_scope("topn_batch")
    def kernel(srcs, mat):
        # per-device: srcs u32[Q, W], mat u32[s_local, R, W].
        # lax.map over sources keeps the popcount intermediate at one
        # [s_local, R, W] buffer instead of Q of them (same trade as
        # ops.intersection_counts_matrix_batch).
        def one(src):
            return jnp.sum(
                jax.lax.population_count(
                    jnp.bitwise_and(mat, src[None, None, :])
                ).astype(jnp.int32),
                axis=-1,
            )  # [s_local, R]

        scores = jax.lax.map(one, srcs)  # [Q, s_local, R]
        q = scores.shape[0]
        counts, ids = jax.lax.top_k(scores, k)  # [Q, s_local, k]
        counts = jax.lax.all_gather(
            counts.reshape(q, -1), SHARD_AXIS, axis=1, tiled=True
        )
        ids = jax.lax.all_gather(ids.reshape(q, -1), SHARD_AXIS, axis=1, tiled=True)
        return ids, counts

    return jax.jit(
        jax.shard_map(
            kernel,
            mesh=mesh,
            in_specs=(P(), P(SHARD_AXIS)),
            out_specs=(P(), P()),
            check_vma=False,
        )
    )


def _filter_program(mesh: Mesh, kernel):
    """``kernel(*arrays)`` as one program over the mesh with a small
    replicated result. Every array of rank two or more is a shard stack
    (its leading dim split over the mesh); a vector is a lowered
    filter's predicates (``executor._eval_tree``), replicated."""

    @jax.jit
    def program(*arrays):
        specs = tuple(P(SHARD_AXIS) if a.ndim > 1 else P() for a in arrays)
        return jax.shard_map(kernel, mesh=mesh, in_specs=specs, out_specs=P())(*arrays)

    return program


def count_stack_spmd(mesh: Mesh, tree):
    """Global popcount of a filter over all shards in one program.

    ``tree`` is the filter's structure and the arguments its inputs, as
    ``Executor._tree_leaves`` lowers them: leaves u32[S, W] or a field's
    planes u32[S, D+1, W], then the predicate vector where the tree has
    ``range`` nodes -> i32 global count. This is the serving executor's
    batched Count terminal: the tree is elementwise over the shard axis,
    so each device evaluates its shards' share and the only collective
    is the final psum — the reference's uint64-sum reduceFn
    (executor.go:966-996) riding ICI. ``("leaf", 0)`` counts one stack.
    """
    from pilosa_tpu.executor.executor import _eval_tree

    @jax.named_scope("count")
    def kernel(*inputs):  # leaves u32[s_local, ...]
        # a filter with no input is all-zero nodes: no rows count 0
        words = _eval_tree(tree, inputs, inputs[0].shape[0] if inputs else 0)
        local = jnp.sum(jax.lax.population_count(words).astype(jnp.int32))
        return jax.lax.psum(local, SHARD_AXIS)

    return _filter_program(mesh, kernel)


def topn_scores_sparse_spmd(mesh: Mesh, k: int):
    """Block-sparse per-shard TopN candidate scoring across the mesh.

    A dense form would stage every candidate row at 128 KB regardless
    of sparsity — at a 50k-candidate ranked cache that is tens of GB
    of staging per query (SURVEY.md §7 hard part 2). Here each shard
    stages only its candidates' nonempty 2^16-bit container blocks,
    padded to a common per-shard block count:

      srcs:   u32[S, W]        per-shard source bitmap (shard-sharded)
      blocks: u32[S, B, 2048]  per-shard candidate container blocks
      brow:   i32[S, B]        local candidate index per block
      bslot:  i32[S, B]        container position within the row

    Padding blocks are zero words aimed at (row 0, slot 0) and
    contribute nothing to an intersection. Returns i32[S, k] scores
    replicated everywhere via all_gather (the reference's HTTP Pairs
    exchange, executor.go:563-585, riding ICI). k is static; callers
    use pow2 chunk sizes so the compile cache stays bounded.
    """
    from pilosa_tpu.ops.packed import CONTAINER_WORDS

    @jax.named_scope("topn_scores_sparse")
    def kernel(srcs, blocks, brow, bslot):
        # per-device: srcs u32[s_local, W], blocks u32[s_local, B, 2048]
        per_shard = srcs.reshape(srcs.shape[0], -1, CONTAINER_WORDS)

        def one(src_blocks, blk, br, bs):
            src_blk = src_blocks[bs]  # [B, 2048]
            pc = jax.lax.population_count(jnp.bitwise_and(blk, src_blk))
            per_block = jnp.sum(pc.astype(jnp.int32), axis=-1)
            return jax.ops.segment_sum(per_block, br, num_segments=k)

        scores = jax.vmap(one)(per_shard, blocks, brow, bslot)  # [s_local, k]
        return jax.lax.all_gather(scores, SHARD_AXIS, axis=0, tiled=True)

    return jax.jit(
        jax.shard_map(
            kernel,
            mesh=mesh,
            in_specs=(P(SHARD_AXIS), P(SHARD_AXIS), P(SHARD_AXIS), P(SHARD_AXIS)),
            out_specs=P(),
            check_vma=False,
        )
    )


def bsi_sum_spmd(mesh: Mesh, bit_depth: int, tree=None):
    """Sum(field) over all shards: per-plane popcounts psum'd over ICI.

    planes: u32[S, D+1, W], then the inputs of the filter whose
    structure ``tree`` is (``count_stack_spmd``; None: an unfiltered Sum
    counts the planes directly, the reference's fragment.sum with nil
    filter). Each device evaluates the filter over its own shards and
    counts: the arithmetic of the one-device program
    (``executor._trace_bsi_sum``), one launch a request. Returns
    i32[D+1] global per-plane counts; host computes Σ counts[i]<<i in
    exact Python ints.
    """
    from pilosa_tpu.executor.executor import _trace_bsi_sum

    @jax.named_scope("plane_counts")
    def kernel(planes, *inputs):  # planes u32[s_local, D+1, W]
        local = _trace_bsi_sum(bit_depth, tree, planes, inputs)
        return jax.lax.psum(local, SHARD_AXIS)

    return _filter_program(mesh, kernel)


def row_algebra_spmd(mesh: Mesh, op: str):
    """Materialising bitmap algebra across shards: fold K rows per shard
    elementwise; result stays sharded (each device keeps its shard's
    result segment — no collective, like the reference's per-node Row
    segments that only merge at the coordinator)."""

    from pilosa_tpu.ops.packed import fold_rows

    @jax.named_scope("row_algebra")
    def kernel(mat):  # u32[s_local, K, W]
        if op == "and":
            init, fn = jnp.uint32(0xFFFFFFFF), jnp.bitwise_and
        elif op == "or":
            init, fn = jnp.uint32(0), jnp.bitwise_or
        else:
            init, fn = jnp.uint32(0), jnp.bitwise_xor
        return jax.lax.reduce(mat, init, fn, (1,))

    return jax.jit(
        jax.shard_map(
            kernel,
            mesh=mesh,
            in_specs=(P(SHARD_AXIS),),
            out_specs=P(SHARD_AXIS),
        )
    )


class ShardBatchPlan:
    """Host-side packing of a set of fragments into one shard-major batch.

    Pads the shard list to the mesh size (empty shards contribute zero
    words — identical results, since AND with missing shard never occurs:
    padding shards carry no query rows and reduce as zeros).
    """

    def __init__(self, mesh: Mesh, shards: list[int]) -> None:
        self.mesh = mesh
        self.n_devices = mesh.devices.size
        self.shards = list(shards)
        pad = (-len(self.shards)) % self.n_devices
        self.padded = self.shards + [-1] * pad

    def stack_rows(self, words_by_shard: dict[int, np.ndarray], width: int) -> np.ndarray:
        """words_by_shard: shard -> u32[K, W]; missing/padding → zeros."""
        k = max((w.shape[0] for w in words_by_shard.values()), default=1)
        out = np.zeros((len(self.padded), k, width), dtype=np.uint32)
        for i, s in enumerate(self.padded):
            w = words_by_shard.get(s)
            if w is not None:
                out[i, : w.shape[0]] = w
        return out
