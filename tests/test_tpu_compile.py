"""Compile the served path's kernels for a described TPU v5e, no chip
attached (guide on-chip-measurement §2, rehearsal 3).

What the chip's compiler refuses — an unsupported primitive in a Pallas
kernel, a program that does not fit device memory, a kernel that cannot
be partitioned — is raised here at real widths (W = 32768 words per
shard row), so it costs no chip time. Nothing runs: a compile that
passes says nothing about results or times.

Only one process at a time may load the TPU's library and it keeps it
until exit, so the topology is described inside a module-scoped fixture
(never at import, in a ``skipif`` or in ``parametrize``), every compile
happens in this process, and all of it lives in this one file.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

from pilosa_tpu import ops
from pilosa_tpu.executor import executor as executor_mod
from pilosa_tpu.executor import fusion
from pilosa_tpu.ops import pallas_kernels
from pilosa_tpu.parallel import spmd

W = 32768  # u32 words per shard row: 2^20 columns
S = 64  # shards in BASELINE config 4
DEPTH = 10  # BSI bit depth of chip_smoke.py's int field


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described device is written to the persistent
    # cache but cannot be read back without a chip: keep these out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    sharding = SingleDeviceSharding(topo.devices[0])

    def shape(dims, dtype=jnp.uint32):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=sharding)

    return shape


@pytest.fixture(scope="module")
def mesh(topo):
    return Mesh(np.array(topo.devices[:4]), (spmd.SHARD_AXIS,))


_CHAIN = (
    "Intersect",
    (
        ("Union", (("leaf", 0), ("leaf", 1))),
        ("Union", (("leaf", 2), ("Difference", (("leaf", 3), ("leaf", 4))))),
    ),
)


def _matrix_batch(s):
    return ops.intersection_counts_matrix_batch.lower(s((32, W)), s((4096, W)))


def _sparse_stacked_mat(s):
    # chip_smoke.py's TopN head chunk: 128 candidates x 64 shards, every
    # one a hot row holding all 16 container blocks (tall64.topn's head)
    b = S * 128 * 16
    i32 = jnp.int32
    return ops.sparse_intersection_counts_stacked_mat.lower(
        s((S, W)), s((b, 16, 128)), s((b,), i32), s((b,), i32), s((b,), i32),
        num_rows=S * 128, n_shards=S, chunk=128,
    )


def _sparse_stacked_second_chunk(s):
    # the largest program chip_smoke.py launches, in the XLA form the
    # scorer keeps for a stack over the one-pass kernel's VMEM budget:
    # 4096 candidates x 64 shards, 385,024 blocks padded to 2^19 — 4 GiB
    # in, 4 GiB of gathered sources
    b = 1 << 19
    i32 = jnp.int32

    def gather_scores(srcs, blocks, brow, bslot, bshard):
        per_block = ops.stacked_block_counts_gather(srcs, blocks, bslot, bshard)
        return jax.ops.segment_sum(per_block, brow, num_segments=S * 4096)

    return jax.jit(gather_scores).lower(
        s((S, W)), s((b, 16, 128)), s((b,), i32), s((b,), i32), s((b,), i32)
    )


def _count_tree(s):
    fn = jax.jit(lambda *ls: ops.count_bits(executor_mod._eval_tree(_CHAIN, ls))[None])
    return fn.lower(*[s((S, W))] * 5)


def _expand_blocks(s):
    i32 = jnp.int32
    return ops.expand_blocks.lower(
        s((65536,)), s((2048,)), s((2048,)), s((16, 2048)), s((16,), i32),
        num_words=16 * W,
    )


def _bsi_sum(s):
    return ops.bsi_plane_counts_batched.lower(
        s((S, DEPTH + 1, W)), s((S, W)), bit_depth=DEPTH, has_filter=True
    )


def _bsi_range(s):
    # the Range leaf as the executor launches it: the kept jit of the
    # vmapped kernel, the predicate traced
    fn = jax.jit(jax.vmap(executor_mod._range_kernel("<", DEPTH), in_axes=(0, None)))
    return fn.lower(s((S, DEPTH + 1, W)), s((), jnp.uint32))


SSB_S = 58  # shards of benchmark/configs/ssb10.json


def _ssb_range_between(s):
    # ssb10.flight1: Range(lo_quantity >< [26, 35]), 6 value planes + existence
    fn = jax.jit(jax.vmap(executor_mod._range_kernel("><", 6), in_axes=(0, None, None)))
    return fn.lower(s((SSB_S, 7, W)), s((), jnp.uint32), s((), jnp.uint32))


# ssb10.flight1's filters as Executor._tree_leaves lowers them: the
# Ranges are structure, their plane stacks leaves, their predicates one
# traced vector after the leaves
_SSB_Q11 = ("Intersect", (("leaf", 0), ("range", "><", 4, 1, (0, 1)), ("range", "<", 6, 2, (2,))))
_SSB_Q13 = (
    "Intersect",
    (("leaf", 0), ("leaf", 1), ("range", "><", 4, 2, (0, 1)), ("range", "><", 6, 3, (2, 3))),
)


def _ssb_inputs(s, rows, predicates):
    # Sum(..., field=lo_revenue_computed): 27 value planes + existence,
    # then the filter's rows, lo_discount's 4 + 1 planes, lo_quantity's 6 + 1
    return [s((SSB_S, 28, W))] + [s((SSB_S, W))] * rows + [s((SSB_S, 5, W)), s((SSB_S, 7, W)), s((predicates,))]


def _ssb_sum(s):
    # a lone Q1.1: the compares and the folds inside the sum's program
    fn = jax.jit(lambda planes, *ls: executor_mod._trace_bsi_sum(27, _SSB_Q11, planes, ls))
    return fn.lower(*_ssb_inputs(s, 1, 3))


def _ssb_wave_of_two(s):
    # Q1.1 and Q1.3 met in one dispatch wave: one fused program
    descs = (("sum", 27, _SSB_Q11, 4), ("sum", 27, _SSB_Q13, 5))
    return jax.jit(fusion._build_program(descs)).lower(*_ssb_inputs(s, 1, 3), *_ssb_inputs(s, 2, 4))


def _groupby_plane_counts(s):
    return ops.groupby_plane_counts.lower(s((12, S * W)), s((DEPTH + 1, S * W)))


def _fused_query(s):
    # Count + Count(chain) + TopN head + Sum in one program, the shape of
    # chip_smoke.py's first multi-call request
    b = S * 128 * 16
    i32 = jnp.int32
    descs = (
        ("count", ("leaf", 0), 1),
        ("count", _CHAIN, 5),
        ("topn", S * 128, S, 128),
        ("sum", DEPTH, ("leaf", 0), 1),
    )
    flat = (
        [s((S, W))] * 6
        + [s((S, W)), s((b, 16, 128)), s((b,), i32), s((b,), i32), s((b,), i32)]
        + [s((S, DEPTH + 1, W)), s((S, W))]
    )
    return jax.jit(fusion._build_program(descs)).lower(*flat)


@pytest.mark.parametrize(
    "lower",
    [
        _matrix_batch,
        _sparse_stacked_mat,
        _sparse_stacked_second_chunk,
        _count_tree,
        _expand_blocks,
        _bsi_sum,
        _bsi_range,
        _ssb_range_between,
        _ssb_sum,
        _ssb_wave_of_two,
        _groupby_plane_counts,
        _fused_query,
    ],
    ids=lambda f: f.__name__.lstrip("_"),
)
def test_served_kernel_compiles_for_v5e(one_chip, lower):
    compiled = lower(one_chip).compile()
    mem = compiled.memory_analysis()
    # 16 GB of HBM: arguments plus scratch of one program must fit
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 15 << 30


# ssb10star.flight2's panels as the fuser lowers them (fusion._lower_groupby):
# the years' 7 rows and the listed brands' rows stacked [R, S·W], the
# filter's staged rows as leaves, lo_revenue's 24 planes + existence
_STAR_Q21 = ("groupby_sum", (7, 40), ("Intersect", (("leaf", 0), ("leaf", 1))), 2, 24)
_STAR_Q22 = ("groupby_sum", (7, 8), ("leaf", 0), 1, 24)
_STAR_Q23 = ("groupby_sum", (7, 1), ("leaf", 0), 1, 24)


@pytest.mark.parametrize("desc", [_STAR_Q21, _STAR_Q22, _STAR_Q23], ids=["q2_1", "q2_2", "q2_3"])
def test_ssb_star_panel_compiles_and_keeps_its_group_matrix_only_at_q2_1(one_chip, desc):
    """Q2.1 (K = 280) keeps the [K, S·W] u32 group matrix that
    ``_lower_groupby`` declares to the admission (2,128,609,280 B; temp
    2,197,222,912 B: PERF.md section 7); at Q2.2's K = 56 and Q2.3's 7
    the compiler recomputes the groups in each popcount and keeps none,
    though the admission is asked for them all the same."""
    s = one_chip
    wf = SSB_S * W
    rows, leaves, depth = desc[1], desc[3], desc[4]
    flat = [s((r, wf)) for r in rows] + [s((SSB_S, W))] * leaves + [s((depth + 1, wf))]
    compiled = jax.jit(fusion._build_program((desc,))).lower(*flat).compile()
    mem = compiled.memory_analysis()
    groups = rows[0] * rows[1] * wf * 4
    # the operands, as the chip lays them out (a stack's rows tiled: Q2.1's 624,951,296 B)
    assert mem.argument_size_in_bytes >= ((sum(rows) + depth + 1) * wf + leaves * SSB_S * W) * 4
    if rows[1] == 40:
        assert groups <= mem.temp_size_in_bytes < groups + (128 << 20)
    else:
        assert mem.temp_size_in_bytes < 64 << 20
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 15 << 30


TAXI_S = 96  # shards of benchmark/configs/taxi96.json


def _onepass_tall64_head(s):
    # tall64.topn's head (and its bounded second chunk): 2^17 blocks,
    # 1 GiB, through the matrix form a fused program traces
    return _sparse_stacked_mat(s)


def _onepass_tall64_alone(s):
    b = 1 << 17
    i32 = jnp.int32
    return pallas_kernels.stacked_block_counts_onepass.lower(
        s((S, W)), s((b, 16, 128)), s((b,), i32), s((b,), i32)
    )


def _onepass_taxi96(s):
    # taxi96.dashboard's filtered TopN: 96 shards, 2^16 blocks
    b = 1 << 16
    i32 = jnp.int32
    return ops.sparse_intersection_counts_stacked.lower(
        s((TAXI_S, W)), s((b, 16, 128)), s((b,), i32), s((b,), i32), s((b,), i32),
        num_rows=TAXI_S * 128,
    )


@pytest.mark.parametrize(
    "lower",
    [_onepass_tall64_head, _onepass_tall64_alone, _onepass_taxi96],
    ids=lambda f: f.__name__.lstrip("_"),
)
def test_onepass_scorer_compiles_for_v5e(one_chip, lower):
    """Lowered for a TPU, the stacked scorer is the one-pass kernel: a
    Mosaic custom call and no gathered [B, 16, 128] temporary (the
    gather form's is the bundle's size, 1 GiB here)."""
    compiled = lower(one_chip).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 64 << 20


def test_the_gather_form_writes_a_temporary_the_size_of_the_bundle(one_chip):
    b = 1 << 17
    i32 = jnp.int32
    s = one_chip
    compiled = (
        jax.jit(ops.stacked_block_counts_gather)
        .lower(s((S, W)), s((b, 16, 128)), s((b,), i32), s((b,), i32))
        .compile()
    )
    assert "tpu_custom_call" not in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes >= b * 2048 * 4


def _count_fold(mesh, s):
    return spmd.count_fold_spmd(mesh).lower(s((S, 3, W)))


def _count_stack(mesh, s):
    return spmd.count_stack_spmd(mesh, ("leaf", 0)).lower(s((S, W)))


def _topn(mesh, s):
    return spmd.topn_spmd(mesh, 16).lower(s((S, W)), s((S, 64, W)))


def _topn_scores_sparse(mesh, s):
    i32 = jnp.int32
    return spmd.topn_scores_sparse_spmd(mesh, 128).lower(
        s((S, W)), s((S, 2048, 2048)), s((S, 2048), i32), s((S, 2048), i32)
    )


def _bsi_sum_mesh(mesh, s):
    return spmd.bsi_sum_spmd(mesh, DEPTH, ("leaf", 0)).lower(
        s((S, DEPTH + 1, W)), s((S, W))
    )


MESH_S = 116  # shards of benchmark/configs/ssb20x4.json, 29 a chip


def _tiled_quarter(shards: int) -> int:
    """A device's quarter of the shards as the chip lays it out: the
    (8, 128) tiling pads it to a multiple of 8, so 29 take 32."""
    return -(-shards // 4 // 8) * 8


def _ssb_mesh_compares(mesh, s):
    # flight 1's Range leaves as Executor._range_launch launches them on
    # a mesh for a consumer that reads an array (a TopN's source since
    # ISSUE 36): the kept jit of the vmapped kernel under GSPMD,
    # planes split on the shard axis, the predicates the host's scalars
    pred = jax.ShapeDtypeStruct((), jnp.uint32)

    def both(discount, quantity, lo, hi, under):
        between = jax.vmap(executor_mod._range_kernel("><", 4), in_axes=(0, None, None))
        less = jax.vmap(executor_mod._range_kernel("<", 6), in_axes=(0, None))
        return between(discount, lo, hi), less(quantity, under)

    return jax.jit(both).lower(s((MESH_S, 5, W)), s((MESH_S, 7, W)), pred, pred, pred)


def _ssb_mesh_and(mesh, s):
    # the eager fold of two materialised stacks, as _bitmap_stack makes it
    return jax.jit(ops.and_).lower(s((MESH_S, W)), s((MESH_S, W)))


def _ssb_mesh_sum(mesh, s):
    # Sum(Row(...), field=lo_revenue_computed): the filter one staged stack
    return spmd.bsi_sum_spmd(mesh, 27, ("leaf", 0)).lower(s((MESH_S, 28, W)), s((MESH_S, W)))


def _ssb_mesh_inlined(mesh, s, tree, rows, predicates):
    # an ssb20x4.flight1 request as it is launched since ISSUE 36: the
    # compares and the folds inside the sum's shard_map kernel, the
    # predicates one u32 vector on every device
    preds = jax.ShapeDtypeStruct((predicates,), jnp.uint32, sharding=NamedSharding(mesh, P()))
    stacks = [s((MESH_S, 28, W)), *(s((MESH_S, W)) for _ in range(rows)), s((MESH_S, 5, W)), s((MESH_S, 7, W))]
    return spmd.bsi_sum_spmd(mesh, 27, tree).lower(*stacks, preds)


def _ssb_mesh_sum_q11(mesh, s):
    return _ssb_mesh_inlined(mesh, s, _SSB_Q11, 1, 3)


def _ssb_mesh_sum_q13(mesh, s):
    return _ssb_mesh_inlined(mesh, s, _SSB_Q13, 2, 4)


@pytest.mark.parametrize(
    "lower",
    [_count_fold, _count_stack, _topn, _topn_scores_sparse, _bsi_sum_mesh,
     _ssb_mesh_compares, _ssb_mesh_and, _ssb_mesh_sum, _ssb_mesh_sum_q11, _ssb_mesh_sum_q13],
    ids=lambda f: f.__name__.strip("_"),
)
def test_spmd_kernel_compiles_for_four_chips(mesh, lower):
    sharding = NamedSharding(mesh, P(spmd.SHARD_AXIS))
    held = []

    def shape(dims, dtype=jnp.uint32):
        held.append(_tiled_quarter(dims[0]) * int(np.prod(dims[1:])) * jnp.dtype(dtype).itemsize)
        return jax.ShapeDtypeStruct(dims, dtype, sharding=sharding)

    compiled = lower(mesh, shape).compile()
    text = compiled.as_text()
    mem = compiled.memory_analysis()
    # each device holds a quarter of every shard-major operand (and a
    # tile for each of a compare's replicated predicates)
    assert 0 <= mem.argument_size_in_bytes - sum(held) <= 3 * 512
    outs = jax.tree_util.tree_leaves(compiled.output_shardings)
    if lower in (_ssb_mesh_compares, _ssb_mesh_and):
        # a filter built on the mesh stays split as its planes are: no
        # collective, and each output a quarter a device
        assert "all-gather" not in text and "all-reduce" not in text and "all-to-all" not in text
        assert all(o.is_equivalent_to(sharding, 2) for o in outs)
        assert 0 <= mem.output_size_in_bytes - len(outs) * _tiled_quarter(MESH_S) * W * 4 <= 512  # a tuple's table
    else:
        # the cross-shard reduce is a collective inside the program (the
        # compiler turns a small all_gather into an all-reduce)
        assert "all-reduce" in text or "all-gather" in text
    if lower in (_ssb_mesh_sum, _ssb_mesh_sum_q11, _ssb_mesh_sum_q13):
        # one all-reduce, of the 28 plane counts; no [S, W] stack is gathered:
        # a device evaluates the filter over its own 29 shards
        assert text.count(" all-reduce(") + text.count(" all-reduce-start(") == 1
        assert "s32[28]" in text and "all-gather" not in text and "all-to-all" not in text
        assert all(o.is_fully_replicated for o in outs)


def _pallas_scores(s):
    return pallas_kernels.intersection_counts_matrix_pallas.lower(
        s((W,)), s((4096, W))
    )


def _pallas_scores_batch(s):
    return pallas_kernels.intersection_counts_matrix_batch_pallas.lower(
        s((8, W)), s((4096, W))
    )


def _pallas_groupby_planes(s):
    return pallas_kernels.groupby_plane_counts_pallas.lower(
        s((DEPTH + 1, W)), s((512, W))
    )


def _pallas_expand_runs(s):
    i32 = jnp.int32
    return pallas_kernels.expand_runs_pallas.lower(
        s((2048,), i32), s((2048,), i32), num_words=W
    )


@pytest.mark.parametrize(
    "lower",
    [
        _pallas_scores,
        _pallas_scores_batch,
        _pallas_groupby_planes,
        _pallas_expand_runs,
    ],
    ids=lambda f: f.__name__.lstrip("_"),
)
def test_pallas_kernel_compiles_for_v5e(one_chip, lower):
    compiled = lower(one_chip).compile()
    assert "tpu_custom_call" in compiled.as_text()
