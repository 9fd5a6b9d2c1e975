"""The one-chip stacked TopN scorer's one-pass kernel, on the CPU.

``pallas_kernels.stacked_block_counts_onepass`` (run here by the Pallas
interpreter) must count every block as the XLA gather does, element for
element; the scorer must take the kernel only where it is lowered for a
TPU with a source stack inside the VMEM budget; and the host must count
``topn.scorer_launches`` once a launch, never once a trace. Compiles for
a described v5e are in tests/test_tpu_compile.py.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pilosa_tpu import SHARD_WIDTH, ops
from pilosa_tpu.core import Holder
from pilosa_tpu.executor import Executor
from pilosa_tpu.ops import pallas_kernels
from pilosa_tpu.utils import metrics

W = 32768  # u32 words per shard row
TILE = pallas_kernels.ONEPASS_TILE


def _bundle(rng, n_shards, n_blocks, pad_to=None, fill=None):
    """A random stacked bundle as the stager lays it out: blocks
    u32[B, 16, 128] of n_blocks real blocks, padded with zero blocks
    aimed at (shard 0, row 0, slot 0) up to ``pad_to``."""
    if fill is None:
        blocks = rng.integers(0, 1 << 32, (n_blocks, 16, 128), dtype=np.uint32)
    else:
        blocks = np.full((n_blocks, 16, 128), fill, dtype=np.uint32)
    shard = np.sort(rng.integers(0, n_shards, n_blocks)).astype(np.int32)
    slot = rng.integers(0, 16, n_blocks).astype(np.int32)
    row = (shard * 8 + rng.integers(0, 8, n_blocks)).astype(np.int32)
    if pad_to is not None and pad_to > n_blocks:
        pad = pad_to - n_blocks
        blocks = np.concatenate([blocks, np.zeros((pad, 16, 128), np.uint32)])
        shard, slot, row = (np.pad(a, (0, pad)) for a in (shard, slot, row))
    return blocks, row, slot, shard


def _srcs(rng, n_shards, fill=None):
    if fill is not None:
        return np.full((n_shards, W), fill, dtype=np.uint32)
    return rng.integers(0, 1 << 32, (n_shards, W), dtype=np.uint32)


def _numpy_counts(srcs, blocks, slot, shard):
    src_blk = srcs.reshape(srcs.shape[0], 16, 16, 128)[shard, slot]
    bits = np.unpackbits((blocks & src_blk).view(np.uint8), axis=1)
    return bits.reshape(blocks.shape[0], -1).sum(axis=1)


def _onepass(srcs, blocks, slot, shard):
    return np.asarray(
        pallas_kernels.stacked_block_counts_onepass(
            srcs, blocks, slot, shard, interpret=True
        )
    )


@pytest.mark.parametrize(
    "n_shards, n_blocks, pad_to",
    [
        (1, 100, 128),  # one shard, B under a tile, pow2 padding
        (3, 700, TILE),  # pow2 padding blocks aimed at (shard 0, row 0)
        (64, 3 * TILE, None),  # tall64's shards, several tiles
        (96, TILE + 300, 2 * TILE),  # taxi96's shards, padded past a tile
    ],
    ids=lambda v: str(v),
)
def test_onepass_counts_every_block_as_the_gather_does(n_shards, n_blocks, pad_to):
    rng = np.random.default_rng(n_shards * 1000 + n_blocks)
    srcs = _srcs(rng, n_shards)
    blocks, row, slot, shard = _bundle(rng, n_shards, n_blocks, pad_to)
    got = _onepass(srcs, blocks, slot, shard)
    want = np.asarray(ops.stacked_block_counts_gather(srcs, blocks, slot, shard))
    np.testing.assert_array_equal(got, want)
    np.testing.assert_array_equal(got, _numpy_counts(srcs, blocks, slot, shard))
    if pad_to is not None:
        assert not got[n_blocks:].any()  # padding counts 0
    # the scorer around it: the segment sum of the kernel's counts is
    # the CPU scorer's answer
    num_rows = n_shards * 8
    scores = np.asarray(
        ops.sparse_intersection_counts_stacked(
            srcs, blocks, row, slot, shard, num_rows=num_rows
        )
    )
    np.testing.assert_array_equal(
        scores, np.bincount(row, weights=got, minlength=num_rows).astype(np.int32)
    )


@pytest.mark.parametrize(
    "src_fill, block_fill, each",
    [
        (0xFFFFFFFF, 0xFFFFFFFF, 65536),  # all-ones blocks: 2^16 bits each
        (0, 0xFFFFFFFF, 0),  # an all-zero source
        (0, None, 0),
    ],
    ids=["all_ones", "zero_source", "zero_source_random_blocks"],
)
def test_onepass_extremes(src_fill, block_fill, each):
    rng = np.random.default_rng(5)
    srcs = _srcs(rng, 3, fill=src_fill)
    blocks, _, slot, shard = _bundle(rng, 3, TILE, fill=block_fill)
    got = _onepass(srcs, blocks, slot, shard)
    assert got.dtype == np.int32 and (got == each).all()


def test_onepass_reads_every_slot_of_every_shard():
    """Each block aimed at its own (shard, slot), all 16 slots of 3
    shards over and over, each source block distinct: a slot or shard
    read off by one would count another block's bits."""
    rng = np.random.default_rng(11)
    n_shards = 3
    srcs = _srcs(rng, n_shards)
    every = np.arange(TILE, dtype=np.int32) % (16 * n_shards)
    shard, slot = every // 16, every % 16
    blocks = rng.integers(0, 1 << 32, (TILE, 16, 128), dtype=np.uint32)
    got = _onepass(srcs, blocks, slot, shard)
    np.testing.assert_array_equal(got, _numpy_counts(srcs, blocks, slot, shard))
    # the same blocks read against the slot beside their own differ
    off = _numpy_counts(srcs, blocks, (slot + 1) % 16, shard)
    assert (got != off).any()


def test_the_rule_takes_the_kernel_only_on_a_tpu_inside_the_budget(monkeypatch):
    budget_shards = pallas_kernels.ONEPASS_VMEM_BUDGET // (W * 4)
    assert budget_shards == 256
    assert jax.default_backend() == "cpu"
    assert ops.stacked_scorer_how(64) == "gather"  # the CPU backend
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert ops.stacked_scorer_how(64) == "onepass"
    assert ops.stacked_scorer_how(96) == "onepass"
    assert ops.stacked_scorer_how(budget_shards) == "onepass"
    assert ops.stacked_scorer_how(budget_shards + 1) == "gather"


@pytest.mark.parametrize("n_shards, traced", [(64, True), (257, False)])
def test_a_stack_over_the_budget_never_traces_the_kernel(n_shards, traced):
    """Within the budget the kernel is staged beside the gather and the
    lowering platform picks one; over it only the gather is staged.
    Shapes alone: nothing of this size is allocated."""
    b = 1 << 12
    args = (
        jax.ShapeDtypeStruct((n_shards, W), jnp.uint32),
        jax.ShapeDtypeStruct((b, 16, 128), jnp.uint32),
        jax.ShapeDtypeStruct((b,), jnp.int32),
        jax.ShapeDtypeStruct((b,), jnp.int32),
    )
    jaxpr = str(jax.make_jaxpr(ops.stacked_block_counts)(*args))
    assert ("pallas_call" in jaxpr) is traced
    # lowered for the CPU, the scorer is the gather alone
    text = ops.sparse_intersection_counts_stacked.lower(
        args[0], args[1], args[2], args[2], args[3], num_rows=n_shards * 8
    ).as_text()
    assert "tpu_custom_call" not in text and "pallas" not in text


def _holder(tmp_path, shards=3):
    h = Holder(str(tmp_path / "data"))
    h.open()
    fld = h.create_index("i").create_field("f")
    rng = np.random.default_rng(2)
    rows, cols = [], []
    for s in range(shards):
        for r in range(12):
            k = 150 + 25 * r
            rows += [r] * k
            cols += (s * SHARD_WIDTH + rng.integers(0, SHARD_WIDTH, k)).tolist()
    fld.import_bits(rows, cols)
    return h


def _launches(how):
    key = metrics._flat_key(
        metrics.TOPN_SCORER_LAUNCHES, metrics._labels_key({"how": how})
    )
    return metrics.snapshot().get(key, 0)


@pytest.mark.parametrize(
    "query",
    [
        "TopN(f, Row(f=0), n=5)",  # the lone scorer's launch (BatchedScorer)
        "Count(Row(f=1))TopN(f, Row(f=0), n=5)",  # the fused head
    ],
    ids=["lone", "fused_head"],
)
def test_launches_are_counted_and_traces_are_not(tmp_path, query):
    """Two requests through one compiled program count 2: the counter
    is the host's, once a launch, and the program compiles once."""
    h = _holder(tmp_path)
    cpu = Executor(h, device_policy="never")
    dev = Executor(h, device_policy="always", dispatch_enabled=False)
    try:
        want = cpu.execute("i", query)
        assert dev.execute("i", query) == want  # stage and compile
        was = _launches("gather")
        jitted = (
            ops.sparse_intersection_counts_stacked._cache_size(),
            len(dev.fuser._programs),
        )
        assert dev.execute("i", query) == want
        assert dev.execute("i", query) == want
        assert _launches("gather") - was == 2
        assert _launches("onepass") == 0  # the CPU backend
        assert (
            ops.sparse_intersection_counts_stacked._cache_size(),
            len(dev.fuser._programs),
        ) == jitted
    finally:
        dev.close()
        h.close()
