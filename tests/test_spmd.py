"""SPMD kernel tests on the 8-device virtual CPU mesh — collectives
(psum/all_gather) validated against host oracles."""

import numpy as np
import pytest
import jax

from pilosa_tpu.parallel import (
    ShardBatchPlan,
    bsi_sum_spmd,
    count_fold_spmd,
    make_mesh,
    put_sharded,
    row_algebra_spmd,
    topn_spmd,
)

W = 128  # words per shard-row for tests


@pytest.fixture(scope="module")
def mesh():
    assert len(jax.devices()) == 8, "tests expect 8 virtual devices"
    return make_mesh()


def rand_words(rng, *shape):
    return rng.integers(0, 2**32, size=shape, dtype=np.uint32)


def popcount(a):
    return int(np.bitwise_count(a).sum())


def test_count_fold_spmd(mesh):
    rng = np.random.default_rng(0)
    stacked = rand_words(rng, 8, 3, W)
    fn = count_fold_spmd(mesh)
    got = int(fn(put_sharded(mesh, stacked)))
    want = sum(
        popcount(stacked[s, 0] & stacked[s, 1] & stacked[s, 2]) for s in range(8)
    )
    assert got == want


def test_count_fold_multiple_shards_per_device(mesh):
    rng = np.random.default_rng(1)
    stacked = rand_words(rng, 16, 2, W)  # 2 shards per device
    fn = count_fold_spmd(mesh)
    got = int(fn(put_sharded(mesh, stacked)))
    want = sum(popcount(stacked[s, 0] & stacked[s, 1]) for s in range(16))
    assert got == want


def test_topn_spmd(mesh):
    rng = np.random.default_rng(2)
    S, R, k = 8, 16, 4
    src = rand_words(rng, S, W)
    mat = rand_words(rng, S, R, W)
    fn = topn_spmd(mesh, k)
    ids, counts = fn(put_sharded(mesh, src), put_sharded(mesh, mat))
    ids, counts = np.asarray(ids), np.asarray(counts)
    assert ids.shape == (S * k,)
    # each shard's k entries must be that shard's true top-k scores
    for s in range(S):
        scores = np.bitwise_count(mat[s] & src[s][None, :]).sum(axis=1)
        want = sorted(scores.tolist(), reverse=True)[:k]
        got = sorted(counts[s * k : (s + 1) * k].tolist(), reverse=True)
        assert got == want, s
        # ids match scores
        for i in range(k):
            assert scores[ids[s * k + i]] == counts[s * k + i]


def test_topn_batch_spmd(mesh):
    from pilosa_tpu.parallel import topn_batch_spmd

    rng = np.random.default_rng(7)
    S, R, Q, k = 8, 16, 4, 3
    srcs = rand_words(rng, Q, W)
    mat = rand_words(rng, S, R, W)
    fn = topn_batch_spmd(mesh, k)
    ids, counts = fn(srcs, put_sharded(mesh, mat))
    ids, counts = np.asarray(ids), np.asarray(counts)
    assert ids.shape == (Q, S * k) and counts.shape == (Q, S * k)
    for q in range(Q):
        for s in range(S):
            scores = np.bitwise_count(mat[s] & srcs[q][None, :]).sum(axis=1)
            want = sorted(scores.tolist(), reverse=True)[:k]
            got = sorted(counts[q, s * k : (s + 1) * k].tolist(), reverse=True)
            assert got == want, (q, s)
            for i in range(k):
                assert scores[ids[q, s * k + i]] == counts[q, s * k + i]


def test_bsi_sum_spmd(mesh):
    rng = np.random.default_rng(3)
    S, D = 8, 6
    planes = rand_words(rng, S, D + 1, W)
    filt = rand_words(rng, S, W)
    fn = bsi_sum_spmd(mesh, D, ("leaf", 0))  # the filter is one staged stack
    counts = np.asarray(fn(put_sharded(mesh, planes), put_sharded(mesh, filt)))
    for i in range(D + 1):
        want = sum(popcount(planes[s, i] & filt[s]) for s in range(S))
        assert int(counts[i]) == want


def test_row_algebra_spmd(mesh):
    rng = np.random.default_rng(4)
    stacked = rand_words(rng, 8, 3, W)
    for op, npfn in [("and", np.bitwise_and), ("or", np.bitwise_or), ("xor", np.bitwise_xor)]:
        fn = row_algebra_spmd(mesh, op)
        got = np.asarray(fn(put_sharded(mesh, stacked)))
        want = npfn.reduce(stacked, axis=1)
        assert np.array_equal(got, want), op


def test_shard_batch_plan_padding(mesh):
    plan = ShardBatchPlan(mesh, [0, 1, 2])  # pads to 8
    assert len(plan.padded) == 8
    rng = np.random.default_rng(5)
    words = {0: rand_words(rng, 2, W), 2: rand_words(rng, 2, W)}
    stacked = plan.stack_rows(words, W)
    assert stacked.shape == (8, 2, W)
    assert np.array_equal(stacked[0], words[0])
    assert not stacked[1].any()
    assert np.array_equal(stacked[2], words[2])
    # padding shards reduce to zero in a count fold
    fn = count_fold_spmd(mesh)
    got = int(fn(put_sharded(mesh, stacked)))
    want = popcount(words[0][0] & words[0][1]) + popcount(words[2][0] & words[2][1])
    assert got == want
