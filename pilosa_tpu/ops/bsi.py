"""Bit-sliced-index (BSI) kernels — Sum/Min/Max/Range as bit-plane algebra.

A BSI field stores an integer per column as bitDepth bit-plane rows plus
a not-null row at plane index bitDepth (reference fragment.go:467-836).
The reference walks roaring containers per plane; here each plane is a
packed u32[W] row and the keep/exclude recurrences become O(bitDepth)
masked word ops — fully vectorised on the VPU and fused by XLA into a
couple of HBM passes.

Every kernel takes ``planes``: u32[D+1, W] where planes[D] is the
not-null (existence) row, and an optional ``filter`` row. ``bit_depth``
is static (a property of the field schema); predicates are *traced*
scalars so varying query constants never trigger recompilation.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def _filtered_exists(planes, filter_row):
    exists = planes[-1]
    if filter_row is not None:
        exists = jnp.bitwise_and(exists, filter_row)
    return exists


@functools.partial(jax.jit, static_argnames=("bit_depth", "has_filter"))
@jax.named_scope("bsi_sum")
def bsi_plane_counts(planes, filter_row, *, bit_depth: int, has_filter: bool):
    """Per-plane intersection counts for Sum (reference fragment.sum:563-597).

    Returns i32[bit_depth+1]: counts[i] = popcount(plane_i & filter) for
    value planes, counts[bit_depth] = filtered existence count. The host
    computes sum = Σ counts[i]<<i in arbitrary-precision Python ints —
    exactness is never at the mercy of device integer width.
    """
    f = filter_row if has_filter else None
    mat = planes if f is None else jnp.bitwise_and(planes, f[None, :])
    pc = jax.lax.population_count(mat)
    return jnp.sum(pc.astype(jnp.int32), axis=-1)


@functools.partial(jax.jit, static_argnames=("bit_depth", "has_filter"))
@jax.named_scope("bsi_min")
def bsi_min(planes, filter_row, *, bit_depth: int, has_filter: bool):
    """Min recurrence (reference fragment.min:599-630).

    Returns (bits: bool[bit_depth], count: i32) where bits[i] is True if
    bit i of the min value is set; the host assembles the value.
    """
    consider = _filtered_exists(planes, filter_row if has_filter else None)
    bits = []
    for ii in reversed(range(bit_depth)):
        x = jnp.bitwise_and(consider, jnp.bitwise_not(planes[ii]))
        cnt = jnp.sum(jax.lax.population_count(x).astype(jnp.int32))
        pred = cnt > 0
        consider = jnp.where(pred, x, consider)
        bits.append(jnp.logical_not(pred))  # bit ii of min is set iff x empty
    count = jnp.sum(jax.lax.population_count(consider).astype(jnp.int32))
    return jnp.stack(bits[::-1]) if bits else jnp.zeros(0, bool), count


@functools.partial(jax.jit, static_argnames=("bit_depth", "has_filter"))
@jax.named_scope("bsi_max")
def bsi_max(planes, filter_row, *, bit_depth: int, has_filter: bool):
    """Max recurrence (reference fragment.max:632-661)."""
    consider = _filtered_exists(planes, filter_row if has_filter else None)
    bits = []
    for ii in reversed(range(bit_depth)):
        x = jnp.bitwise_and(planes[ii], consider)
        cnt = jnp.sum(jax.lax.population_count(x).astype(jnp.int32))
        pred = cnt > 0
        consider = jnp.where(pred, x, consider)
        bits.append(pred)  # bit ii of max is set iff intersection nonempty
    count = jnp.sum(jax.lax.population_count(consider).astype(jnp.int32))
    return jnp.stack(bits[::-1]) if bits else jnp.zeros(0, bool), count


def _pred_bit(predicate, i):
    return jnp.bitwise_and(jnp.right_shift(predicate, jnp.uint32(i)), jnp.uint32(1)) == 1


@functools.partial(jax.jit, static_argnames=("bit_depth",))
@jax.named_scope("bsi_range")
def bsi_range_eq(planes, predicate, *, bit_depth: int):
    """EQ: keep columns whose every bit matches (reference rangeEQ:678-694)."""
    b = planes[-1]
    for i in reversed(range(bit_depth)):
        bit = _pred_bit(predicate, i)
        row = planes[i]
        b = jnp.where(bit, jnp.bitwise_and(b, row), jnp.bitwise_and(b, jnp.bitwise_not(row)))
    return b


@functools.partial(jax.jit, static_argnames=("bit_depth",))
@jax.named_scope("bsi_range")
def bsi_range_neq(planes, predicate, *, bit_depth: int):
    """NEQ = not-null minus EQ (reference rangeNEQ:696-710)."""
    eq = bsi_range_eq(planes, predicate, bit_depth=bit_depth)
    return jnp.bitwise_and(planes[-1], jnp.bitwise_not(eq))


@functools.partial(jax.jit, static_argnames=("bit_depth", "allow_equality"))
@jax.named_scope("bsi_range")
def bsi_range_lt(planes, predicate, *, bit_depth: int, allow_equality: bool):
    """LT / LTE keep-exclude recurrence (reference rangeLT:712-760).

    The reference short-circuits with `continue`/early-return on
    predicate bits; here those become masked selects on a traced
    predicate so one compiled kernel serves every constant.
    """
    zero = jnp.zeros_like(planes[-1])
    b = planes[-1]
    keep = zero
    leading = jnp.bool_(True)
    ret = zero
    returned = jnp.bool_(False)
    for i in reversed(range(bit_depth)):
        row = planes[i]
        bit = _pred_bit(predicate, i)
        # Leading-zero skip: while in leading zeros and bit==0, just strip rows.
        in_lz = jnp.logical_and(leading, jnp.logical_not(bit))
        b = jnp.where(in_lz, jnp.bitwise_and(b, jnp.bitwise_not(row)), b)
        leading = in_lz
        active = jnp.logical_not(in_lz)
        if i == 0 and not allow_equality:
            # bit==0 -> keep only already-kept; bit==1 -> b \ (row \ keep)
            final = jnp.where(
                bit,
                jnp.bitwise_and(b, jnp.bitwise_not(jnp.bitwise_and(row, jnp.bitwise_not(keep)))),
                keep,
            )
            ret = jnp.where(jnp.logical_and(active, jnp.logical_not(returned)), final, ret)
            returned = jnp.logical_or(returned, active)
            continue
        # bit==0: remove set columns not already kept.
        b0 = jnp.bitwise_and(b, jnp.bitwise_not(jnp.bitwise_and(row, jnp.bitwise_not(keep))))
        b = jnp.where(jnp.logical_and(active, jnp.logical_not(bit)), b0, b)
        # bit==1 (i>0): extend keep with columns having this bit unset.
        if i > 0:
            k1 = jnp.bitwise_or(keep, jnp.bitwise_and(b, jnp.bitwise_not(row)))
            keep = jnp.where(jnp.logical_and(active, bit), k1, keep)
    if not allow_equality and bit_depth > 0:
        return jnp.where(returned, ret, b)
    return b


@functools.partial(jax.jit, static_argnames=("bit_depth", "allow_equality"))
@jax.named_scope("bsi_range")
def bsi_range_gt(planes, predicate, *, bit_depth: int, allow_equality: bool):
    """GT / GTE recurrence (reference rangeGT:762-797)."""
    zero = jnp.zeros_like(planes[-1])
    b = planes[-1]
    keep = zero
    ret = zero
    returned = jnp.bool_(False)
    for i in reversed(range(bit_depth)):
        row = planes[i]
        bit = _pred_bit(predicate, i)
        if i == 0 and not allow_equality:
            # bit==1 -> only kept; bit==0 -> b \ ((b \ row) \ keep)
            bd = jnp.bitwise_and(b, jnp.bitwise_not(row))  # b \ row
            final0 = jnp.bitwise_and(b, jnp.bitwise_not(jnp.bitwise_and(bd, jnp.bitwise_not(keep))))
            final = jnp.where(bit, keep, final0)
            ret = jnp.where(returned, ret, final)
            returned = jnp.bool_(True)
            continue
        # bit==1: remove unset columns not already kept.
        bd = jnp.bitwise_and(b, jnp.bitwise_not(row))
        b1 = jnp.bitwise_and(b, jnp.bitwise_not(jnp.bitwise_and(bd, jnp.bitwise_not(keep))))
        b = jnp.where(bit, b1, b)
        # bit==0 (i>0): extend keep with columns having this bit set.
        if i > 0:
            k0 = jnp.bitwise_or(keep, jnp.bitwise_and(b, row))
            keep = jnp.where(bit, keep, k0)
    if not allow_equality and bit_depth > 0:
        return jnp.where(returned, ret, b)
    return b


@functools.partial(jax.jit, static_argnames=("bit_depth",))
@jax.named_scope("bsi_range")
def bsi_range_between(planes, pred_min, pred_max, *, bit_depth: int):
    """BETWEEN (inclusive both ends) — fused GTE(min) ∧ LTE(max) recurrence
    (reference rangeBetween:806-840)."""
    zero = jnp.zeros_like(planes[-1])
    b = planes[-1]
    keep1 = zero  # GTE side
    keep2 = zero  # LTE side
    for i in reversed(range(bit_depth)):
        row = planes[i]
        bit1 = _pred_bit(pred_min, i)
        bit2 = _pred_bit(pred_max, i)
        # GTE pred_min
        bd = jnp.bitwise_and(b, jnp.bitwise_not(row))
        b_hi = jnp.bitwise_and(b, jnp.bitwise_not(jnp.bitwise_and(bd, jnp.bitwise_not(keep1))))
        b = jnp.where(bit1, b_hi, b)
        if i > 0:
            k1 = jnp.bitwise_or(keep1, jnp.bitwise_and(b, row))
            keep1 = jnp.where(bit1, keep1, k1)
        # LTE pred_max
        b_lo = jnp.bitwise_and(b, jnp.bitwise_not(jnp.bitwise_and(row, jnp.bitwise_not(keep2))))
        b = jnp.where(bit2, b, b_lo)
        if i > 0:
            k2 = jnp.bitwise_or(keep2, jnp.bitwise_and(b, jnp.bitwise_not(row)))
            keep2 = jnp.where(bit2, k2, keep2)
    return b


@functools.partial(jax.jit, static_argnames=("bit_depth", "has_filter"))
@jax.named_scope("bsi_sum")
def bsi_plane_counts_batched(planes, filter_rows, *, bit_depth: int, has_filter: bool):
    """Shard-batched Sum: planes u32[S, D+1, W], filter u32[S, W] →
    i32[D+1] summed over shards in one dispatch."""
    if has_filter:
        block = jnp.bitwise_and(planes, filter_rows[:, None, :])
    else:
        block = planes
    pc = jax.lax.population_count(block)
    return jnp.sum(pc.astype(jnp.int32), axis=(0, 2))


# -- device-resident analytics (GroupBy / Distinct / Percentile) -------------


@functools.partial(jax.jit, static_argnames=("bit_depth", "has_filter"))
@jax.named_scope("bsi_percentile")
def bsi_percentile_batched(planes, filter_rows, nth_bp, *, bit_depth: int, has_filter: bool):
    """Shard-batched nearest-rank percentile as a bit-sliced binary
    search over the value planes (one launch for the whole shard set).

    planes: u32[S, D+1, W]; nth_bp: traced i32 percentile in BASIS
    POINTS (95.5% → 9550) so the target rank k = ceil(nth·n/100) is
    exact integer arithmetic — never at the mercy of f32 rounding. The
    descent walks planes high→low: if ≥k considered columns have bit i
    clear, the k-th smallest has bit i clear and the zeros subset is
    kept; otherwise bit i is set and k drops by the zeros count.

    Returns (bits: bool[bit_depth], count: i32) with bits[i] = bit i of
    the k-th smallest stored value; count is the considered-column
    total (count == 0 means no value exists — bits are garbage then and
    the host must answer empty).
    """
    consider = planes[:, -1, :]
    if has_filter:
        consider = jnp.bitwise_and(consider, filter_rows)
    count = jnp.sum(jax.lax.population_count(consider).astype(jnp.int32))
    # k = ceil(nth_bp * count / 10000) without i32 overflow: split count
    # into q·10000 + r so both partial products stay far below 2^31.
    q = count // 10000
    r = count % 10000
    k = nth_bp * q + (nth_bp * r + 9999) // 10000
    k = jnp.clip(k, 1, jnp.maximum(count, 1))
    bits = []
    for i in reversed(range(bit_depth)):
        plane = planes[:, i, :]
        zeros = jnp.bitwise_and(consider, jnp.bitwise_not(plane))
        c = jnp.sum(jax.lax.population_count(zeros).astype(jnp.int32))
        pred = k <= c
        bits.append(jnp.logical_not(pred))
        consider = jnp.where(pred, zeros, jnp.bitwise_and(consider, plane))
        k = jnp.where(pred, k, k - c)
    bits_arr = jnp.stack(bits[::-1]) if bits else jnp.zeros(0, bool)
    return bits_arr, count


@functools.partial(jax.jit, static_argnames=("bit_depth", "has_filter"))
@jax.named_scope("bsi_distinct")
def bsi_distinct_presence(planes, filter_rows, *, bit_depth: int, has_filter: bool):
    """Distinct(field) as an OR-reduction over BSI planes with
    on-device id extraction: planes u32[S, D+1, W] → packed u32
    presence words over the value domain [0, 2^bit_depth).

    Per shard, each existing (and filtered) column's stored value is
    reassembled from its plane bits and scattered into a presence
    bitmap; shards OR-reduce in a fori_loop so the transient stays one
    shard wide. The result is itself a packed bitmap — the host decodes
    set positions to sorted values (pos + bsig.min) and cross-gang
    merges are plain ORs. Callers gate bit_depth (the presence bitmap
    is 2^bit_depth bits) before choosing this path.
    """
    nshards = planes.shape[0]
    ncols = planes.shape[2] * 32
    domain = 1 << bit_depth
    nwords = max((domain + 31) // 32, 1)
    bitpos = jnp.arange(32, dtype=jnp.uint32)

    def unpack(words):  # u32[W] -> bool[W*32], bit p at index p
        return (
            (words[:, None] >> bitpos[None, :]) & jnp.uint32(1)
        ).astype(jnp.bool_).reshape(-1)

    def shard_presence(sp, filt):
        exists = sp[-1]
        if has_filter:
            exists = jnp.bitwise_and(exists, filt)
        vals = jnp.zeros((ncols,), jnp.int32)
        for i in range(bit_depth):
            vals = vals | (unpack(sp[i]).astype(jnp.int32) << i)
        # absent columns index out of bounds and drop from the scatter
        idx = jnp.where(unpack(exists), vals, jnp.int32(domain))
        return jnp.zeros((domain,), jnp.bool_).at[idx].set(True, mode="drop")

    pres = jax.lax.fori_loop(
        0,
        nshards,
        lambda s, acc: acc | shard_presence(planes[s], filter_rows[s]),
        jnp.zeros((domain,), jnp.bool_),
    )
    total = nwords * 32
    if total != domain:
        pres = jnp.pad(pres, (0, total - domain))
    return jnp.sum(
        pres.reshape(nwords, 32).astype(jnp.uint32) << bitpos[None, :],
        axis=1,
        dtype=jnp.uint32,
    )
