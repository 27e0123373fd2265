"""Device-kernel tests, diffed against naive Python oracles
(reference tier: TestGroupByHash / TestHashJoinOperator golden-page style,
SURVEY §4.1)."""

import collections

import numpy as np
import pytest

jnp = pytest.importorskip("jax.numpy")
import jax  # noqa: E402

from presto_tpu import types as T  # noqa: E402
from presto_tpu.ops import join as J  # noqa: E402
from presto_tpu.ops.filter import selected_positions  # noqa: E402
from presto_tpu.ops.groupby import global_aggregate, grouped_aggregate  # noqa: E402
from presto_tpu.ops.hashing import partition_of, row_hash  # noqa: E402
from presto_tpu.ops.sort import sort_permutation  # noqa: E402


def pad_to(a, cap, fill=0):
    a = np.asarray(a)
    out = np.full(cap, fill, a.dtype)
    out[: len(a)] = a
    return out


# ---------------------------------------------------------------------------
# grouped aggregation
# ---------------------------------------------------------------------------

def test_grouped_aggregate_single_key():
    rng = np.random.default_rng(0)
    n, cap, gcap = 1000, 1024, 64
    keys = rng.integers(0, 37, n).astype(np.int64)
    vals = rng.integers(-100, 100, n).astype(np.int64)
    gi, ng, results = grouped_aggregate(
        [(jnp.asarray(pad_to(keys, cap)), None, T.BIGINT)],
        [("sum", jnp.asarray(pad_to(vals, cap)), None),
         ("count", jnp.asarray(pad_to(vals, cap)), None),
         ("min", jnp.asarray(pad_to(vals, cap)), None),
         ("max", jnp.asarray(pad_to(vals, cap)), None)],
        jnp.asarray(n), gcap)
    ng = int(ng)
    expected = {}
    for k, v in zip(keys, vals):
        e = expected.setdefault(k, [0, 0, 10**9, -10**9])
        e[0] += v
        e[1] += 1
        e[2] = min(e[2], v)
        e[3] = max(e[3], v)
    assert ng == len(expected)
    out_keys = np.asarray(jnp.asarray(pad_to(keys, cap))[gi])[:ng]
    sums = np.asarray(results[0][0])[:ng]
    cnts = np.asarray(results[1][0])[:ng]
    mins = np.asarray(results[2][0])[:ng]
    maxs = np.asarray(results[3][0])[:ng]
    assert sorted(out_keys) == sorted(expected)
    for k, s, c, lo, hi in zip(out_keys, sums, cnts, mins, maxs):
        e = expected[k]
        assert (s, c, lo, hi) == (e[0], e[1], e[2], e[3])


def test_grouped_aggregate_multi_key_with_nulls():
    # keys: (a, b) where b has nulls; SQL groups nulls together
    a = np.array([1, 1, 2, 2, 1, 1], dtype=np.int64)
    b = np.array([10, 10, 20, 20, 0, 0], dtype=np.int64)
    bvalid = np.array([True, True, True, True, False, False])
    v = np.array([1.0, 2.0, 3.0, 4.0, 5.0, 6.0])
    cap, gcap = 8, 8
    gi, ng, results = grouped_aggregate(
        [(jnp.asarray(pad_to(a, cap)), None, T.BIGINT),
         (jnp.asarray(pad_to(b, cap)), jnp.asarray(pad_to(bvalid, cap)),
          T.BIGINT)],
        [("sum", jnp.asarray(pad_to(v, cap)), None)],
        jnp.asarray(6), gcap)
    assert int(ng) == 3
    sums = sorted(np.asarray(results[0][0])[:3].tolist())
    assert sums == [3.0, 7.0, 11.0]


def test_grouped_aggregate_null_values_and_overflow():
    # agg input nulls are ignored; count counts non-null only
    k = np.array([1, 1, 2], dtype=np.int64)
    v = np.array([5.0, 0.0, 7.0])
    vvalid = np.array([True, False, True])
    gi, ng, results = grouped_aggregate(
        [(jnp.asarray(pad_to(k, 4)), None, T.BIGINT)],
        [("sum", jnp.asarray(pad_to(v, 4)), jnp.asarray(pad_to(vvalid, 4))),
         ("count", jnp.asarray(pad_to(v, 4)), jnp.asarray(pad_to(vvalid, 4)))],
        jnp.asarray(3), 8)
    assert int(ng) == 2
    cnt = np.asarray(results[1][0])[:2]
    assert sorted(cnt.tolist()) == [1, 1]
    # overflow: 5 distinct keys, capacity 4 -> num_groups reports 5
    k5 = np.arange(5, dtype=np.int64)
    gi, ng, _ = grouped_aggregate(
        [(jnp.asarray(pad_to(k5, 8)), None, T.BIGINT)],
        [("count", jnp.asarray(pad_to(k5, 8)), None)],
        jnp.asarray(5), 4)
    assert int(ng) == 5  # caller re-runs with bigger capacity


def test_grouped_aggregate_empty():
    gi, ng, results = grouped_aggregate(
        [(jnp.zeros(8, jnp.int64), None, T.BIGINT)],
        [("sum", jnp.zeros(8, jnp.float64), None)],
        jnp.asarray(0), 4)
    assert int(ng) == 0


def test_global_aggregate():
    v = np.array([1.0, 2.0, 3.0, 0.0])
    valid = np.array([True, True, False, True])
    results = global_aggregate(
        [("sum", jnp.asarray(v), jnp.asarray(valid)),
         ("count", jnp.asarray(v), jnp.asarray(valid)),
         ("min", jnp.asarray(v), jnp.asarray(valid)),
         ("max", jnp.asarray(v), jnp.asarray(valid))],
        jnp.asarray(4))
    assert float(results[0][0]) == 3.0  # 1 + 2 + 0 (3.0 is NULL)
    assert int(results[1][0]) == 3
    assert float(results[2][0]) == 0.0
    assert float(results[3][0]) == 2.0


def test_global_aggregate_empty_input():
    results = global_aggregate(
        [("sum", jnp.zeros(4), None)], jnp.asarray(0))
    assert int(results[0][1]) == 0  # count 0 -> SQL NULL sum


# ---------------------------------------------------------------------------
# join
# ---------------------------------------------------------------------------

def reference_inner_join(bkeys, pkeys):
    build_pos = collections.defaultdict(list)
    for i, k in enumerate(bkeys):
        build_pos[k].append(i)
    out = []
    for j, k in enumerate(pkeys):
        for i in build_pos.get(k, []):
            out.append((j, i))
    return out


def run_join(bkeys, pkeys, cap_b=None, cap_p=None, out_cap=64):
    cap_b = cap_b or len(bkeys)
    cap_p = cap_p or len(pkeys)
    bids, pids = J.single_word_ids(
        (jnp.asarray(pad_to(bkeys, cap_b)), None, T.BIGINT),
        (jnp.asarray(pad_to(pkeys, cap_p)), None, T.BIGINT),
        jnp.asarray(len(bkeys)), jnp.asarray(len(pkeys)))
    sb, perm_b = J.build_index(bids)
    lo, counts = J.probe_counts(sb, perm_b, pids)
    return bids, pids, sb, perm_b, lo, counts


def test_inner_join_with_duplicates():
    bkeys = [1, 2, 2, 3, 5]
    pkeys = [2, 3, 4, 2, 1]
    bids, pids, sb, perm_b, lo, counts = run_join(bkeys, pkeys)
    probe_idx, build_idx, valid, _, total = J.expand_matches(
        lo, counts, perm_b, 16)
    got = sorted((int(p), int(b)) for p, b, ok in
                 zip(probe_idx, build_idx, valid) if ok)
    assert got == sorted(reference_inner_join(bkeys, pkeys))
    assert int(total) == len(got)


def test_left_outer_join():
    bkeys = [1, 2, 2]
    pkeys = [2, 4, 1]
    bids, pids, sb, perm_b, lo, counts = run_join(bkeys, pkeys)
    live = pids >= 0
    probe_idx, build_idx, valid, unmatched, total = J.expand_matches_outer(
        lo, counts, live, perm_b, 16)
    rows = [(int(p), int(b), bool(u)) for p, b, u, ok in
            zip(probe_idx, build_idx, unmatched, valid) if ok]
    assert int(total) == 4
    # probe row 1 (key 4) must appear exactly once, unmatched
    assert (1, 0, True) in rows
    matched = [(p, b) for p, b, u in rows if not u]
    assert sorted(matched) == [(0, 1), (0, 2), (2, 0)]


def test_semi_anti():
    bkeys = [2, 3]
    pkeys = [1, 2, 3, 4]
    bids, pids, sb, perm_b, lo, counts = run_join(bkeys, pkeys)
    live = pids >= 0
    semi = np.asarray(J.semi_mask(counts, live, anti=False))
    anti = np.asarray(J.semi_mask(counts, live, anti=True))
    assert semi.tolist() == [False, True, True, False]
    assert anti.tolist() == [True, False, False, True]


def test_null_keys_never_match():
    cap = 4
    bvals = jnp.asarray(pad_to([1, 2], cap))
    bvalid = jnp.asarray(pad_to([True, False], cap))
    pvals = jnp.asarray(pad_to([1, 2], cap))
    pvalid = jnp.asarray(pad_to([False, True], cap))
    bids, pids = J.single_word_ids(
        (bvals, bvalid, T.BIGINT), (pvals, pvalid, T.BIGINT),
        jnp.asarray(2), jnp.asarray(2))
    sb, perm_b = J.build_index(bids)
    lo, counts = J.probe_counts(sb, perm_b, pids)
    assert np.asarray(counts).tolist() == [0, 0, 0, 0]


def test_multi_key_canonical_ids():
    bk = [(1, 10), (1, 20), (2, 10)]
    pk = [(1, 10), (2, 10), (2, 20), (1, 20)]
    cap = 4
    build_cols = [
        (jnp.asarray(pad_to([a for a, _ in bk], cap)), None, T.BIGINT),
        (jnp.asarray(pad_to([b for _, b in bk], cap)), None, T.BIGINT)]
    probe_cols = [
        (jnp.asarray(pad_to([a for a, _ in pk], cap)), None, T.BIGINT),
        (jnp.asarray(pad_to([b for _, b in pk], cap)), None, T.BIGINT)]
    bids, pids = J.canonical_ids(build_cols, probe_cols,
                                 jnp.asarray(3), jnp.asarray(4))
    sb, perm_b = J.build_index(bids)
    lo, counts = J.probe_counts(sb, perm_b, pids)
    probe_idx, build_idx, valid, _, total = J.expand_matches(
        lo, counts, perm_b, 16)
    got = sorted((int(p), int(b)) for p, b, ok in
                 zip(probe_idx, build_idx, valid) if ok)
    assert got == sorted(reference_inner_join(bk, pk))


def test_search_path_probe_key_equals_build_max():
    """Wide key span forces the binary-search fallback; probe keys equal
    to the build-side max must emit exactly one row each (regression:
    _lower_bound without the lo<hi guard overshot to n+1 and
    duplicated every max-key match)."""
    span = 40_000  # > dense scratch minimum (1 << 14)
    bkeys = [0, 7, 7, span]
    pkeys = [span, span, 7, -3]
    bids, pids, sb, perm_b, lo, counts = run_join(bkeys, pkeys)
    assert np.asarray(counts).tolist() == [1, 1, 2, 0]
    probe_idx, build_idx, valid, _, total = J.expand_matches(
        lo, counts, perm_b, 16)
    got = sorted((int(p), int(b)) for p, b, ok in
                 zip(probe_idx, build_idx, valid) if ok)
    assert got == sorted(reference_inner_join(bkeys, pkeys))


def test_matched_build_mask():
    bkeys = [1, 2, 2, 9]
    pkeys = [2, 7]
    bids, pids, sb, perm_b, lo, counts = run_join(bkeys, pkeys)
    matched = np.asarray(J.matched_build_mask(lo, counts, 4, perm_b))
    assert matched.tolist() == [False, True, True, False]


def test_join_overflow_reports_total():
    bkeys = [1] * 10
    pkeys = [1] * 10
    bids, pids, sb, perm_b, lo, counts = run_join(bkeys, pkeys)
    _, _, valid, _, total = J.expand_matches(lo, counts, perm_b, 16)
    assert int(total) == 100  # exceeds out_cap; host re-runs bigger
    assert int(np.asarray(valid).sum()) == 16


# ---------------------------------------------------------------------------
# the direct-address index against the sorted and the hash tiers
# ---------------------------------------------------------------------------
# key tuples per row, None = SQL NULL: (build rows, probe rows)
_EDGE = 1 << 10       # the smallest bucket of ops.join.dense_index_size
LOOKUP_CASES = {
    "unique": ([(5,), (1,), (9,), (3,)],
               [(1,), (2,), (3,), (9,), (9,), (10,)]),
    "duplicates": ([(2,), (2,), (3,), (2,), (7,), (3,)],
                   [(3,), (2,), (4,), (7,), (2,)]),
    "negative": ([(-5,), (-1,), (-5,), (4,)],
                 [(-5,), (-6,), (4,), (0,), (-1,)]),
    "null_build": ([(1,), (None,), (2,)], [(1,), (2,), (3,)]),
    "null_probe": ([(1,), (2,)], [(None,), (1,), (None,), (2,), (5,)]),
    "empty_build": ([], [(1,), (2,)]),
    "below_and_above": ([(10,), (20,), (20,)],
                        [(9,), (-100,), (21,), (1 << 40,), (10,), (20,)]),
    # 'single' ids start at 2: keys 0.._EDGE-3 fill the bucket exactly,
    # one more key takes the next bucket
    "bucket_edge_fits": ([(0,), (_EDGE - 3,)],
                         [(0,), (_EDGE - 3,), (_EDGE - 2,), (_EDGE,)]),
    "bucket_edge_over": ([(0,), (_EDGE - 2,)],
                         [(0,), (_EDGE - 3,), (_EDGE - 2,), (_EDGE,)]),
    "over_the_bound": ([(0,), (3,), (J.DENSE_INDEX_MAX_SLOTS,)],
                       [(0,), (J.DENSE_INDEX_MAX_SLOTS,), (7,)]),
    "packed_two_channel": ([(1, 10), (1, 20), (2, 10), (2, 10), (3, -4)],
                           [(1, 10), (2, 10), (2, 20), (1, 20), (3, 10),
                            (0, 10), (1, None), (3, -4)]),
    "packed_null_build": ([(1, 10), (None, 10), (2, None), (2, 20)],
                          [(1, 10), (2, 20), (2, 10), (None, 10)]),
}
# cases only the operators see, whose keys leave the index for a reason
# other than LOOKUP_CASES' "over_the_bound": (build rows, probe rows,
# key type, the tier HashBuildOperator.finish picks on the CPU)
_WIDE = 1 << 13       # two spans of _WIDE + 1: a product past the index
OPERATOR_CASES = {
    # codes of ONE dictionary (the operators' contract; both sides are
    # coded into it below): the codes are the ids, so the index
    "varchar": ([("ash",), ("elm",), ("elm",), (None,), ("oak",)],
                [("elm",), ("fir",), (None,), ("ash",), ("oak",),
                 ("oak",)], T.VARCHAR, "dense"),
    # not an integer word: the open-addressing table, on every backend
    "double": ([(0.5,), (-2.25,), (0.5,), (None,), (1e300,)],
               [(0.5,), (1e300,), (0.25,), (None,), (-2.25,)],
               T.DOUBLE, "hash"),
    # the packed ids fit int64 and not the index: sorted ids, searched
    "packed_over_the_bound": (
        [(0, 0), (_WIDE, _WIDE), (5, -7), (5, -7), (_WIDE, 0)],
        [(5, -7), (_WIDE, _WIDE), (0, 1), (_WIDE, 0), (0, 0), (5, None)],
        T.BIGINT, "sorted"),
    # a span of 2**62 and more would overflow (value - min + 2): no ids
    # at all, the table compares the key words themselves
    "span_past_the_ids": (
        [(-(1 << 61),), (1 << 61,), (7,), (7,)],
        [(7,), (1 << 61,), (-(1 << 61),), (0,), (None,)],
        T.BIGINT, "hash"),
}
_DENSE_SIZES = {"bucket_edge_fits": _EDGE, "bucket_edge_over": 2 * _EDGE,
                "over_the_bound": None}


def _is_null(key):
    return any(k is None for k in key)


def _ref_matches(bkeys, pkeys):
    """probe row -> the build rows with an equal, non-null key."""
    return {j: [i for i, b in enumerate(bkeys)
                if not _is_null(b) and not _is_null(p) and b == p]
            for j, p in enumerate(pkeys)}


def _key_cols(keys, width, cap):
    """[(values, valid|None, type)] per key channel, padded to ``cap``."""
    cols = []
    for c in range(width):
        vals = [0 if k[c] is None else k[c] for k in keys]
        valid = [k[c] is not None for k in keys]
        cols.append((jnp.asarray(pad_to(np.asarray(vals, np.int64), cap)),
                     None if all(valid)
                     else jnp.asarray(pad_to(np.asarray(valid, bool), cap)),
                     T.BIGINT))
    return cols


def _tier_ranges(bkeys, pkeys):
    """{tier: (lo, counts, perm)} of the three lookups over one build
    and one probe batch, through the id arithmetic the operators use."""
    from presto_tpu.exec.joinop import _ids_from_pairs, _packed_ids
    from presto_tpu.ops.hashtable import pages_hash_build, pages_hash_probe

    width = len((bkeys or pkeys)[0])
    cap = 16
    bcols = _key_cols(bkeys, width, cap)
    pcols = _key_cols(pkeys, width, cap)
    nb, np_ = jnp.asarray(len(bkeys)), jnp.asarray(len(pkeys))
    live = [k for k in bkeys if not _is_null(k)]
    los = np.asarray([min((k[c] for k in live), default=0)
                      for c in range(width)], np.int64)
    his = np.asarray([max((k[c] for k in live), default=0)
                      for c in range(width)], np.int64)
    strides, span = [], 1
    for lo, hi in zip(los, his):
        strides.append(span)
        span *= int(hi) - int(lo) + 1
    strides = np.asarray(strides, np.int64)
    id_base = 2 if width == 1 else 0
    bids, _ = _packed_ids([(v, g) for v, g, _ in bcols], jnp.asarray(los),
                          jnp.asarray(strides), nb)
    bids = jnp.where(bids >= 0, bids + id_base, bids)
    if width == 1:
        pids = _ids_from_pairs(jnp, [(v, g) for v, g, _ in pcols], [0],
                               "single", jnp.asarray(los[0]), None, None,
                               np_)
    else:
        pids = _ids_from_pairs(jnp, [(v, g) for v, g, _ in pcols],
                               list(range(width)), "packed", los, strides,
                               his, np_)
    out = {}
    sb, perm = J.build_index(bids)
    out["sorted"] = J.probe_counts(sb, perm, pids) + (perm,)
    table = pages_hash_build(bcols, nb, 64)
    assert bool(table[7])
    lo, counts, _ = pages_hash_probe(table[:5], pcols, np_)
    out["hash"] = (lo, counts, table[5])
    size = J.dense_index_size(span + id_base)
    if size is not None:
        index, perm = J.build_dense_index(bids, size)
        out["dense"] = J.probe_dense(index, pids) + (perm,)
    return out, size


@pytest.mark.parametrize("case", sorted(LOOKUP_CASES))
def test_probe_dense_parity_with_sorted_and_hash(case):
    """Every tier answers (lo, counts) into its own perm: the counts and
    the build rows each probe row reaches must be the same three times,
    and what a nested loop says."""
    bkeys, pkeys = LOOKUP_CASES[case]
    tiers, size = _tier_ranges(bkeys, pkeys)
    if case in _DENSE_SIZES:
        assert size == _DENSE_SIZES[case]
    assert ("dense" in tiers) == (case != "over_the_bound")
    want = _ref_matches(bkeys, pkeys)
    for tier, (lo, counts, perm) in tiers.items():
        lo, counts, perm = (np.asarray(x) for x in (lo, counts, perm))
        assert counts[len(pkeys):].tolist() == [0] * (16 - len(pkeys)), tier
        for j in range(len(pkeys)):
            got = sorted(perm[lo[j]:lo[j] + counts[j]].tolist())
            assert got == want[j], (tier, j)


JOIN_KINDS = ["inner", "left", "semi", "anti", "notin"]


def _ref_join_rows(bkeys, pkeys, kind):
    m = _ref_matches(bkeys, pkeys)
    rows_p = range(len(pkeys))
    if kind == "inner":
        return sorted((j, i) for j in rows_p for i in m[j])
    if kind == "left":
        return sorted([(j, i) for j in rows_p for i in m[j]]
                      + [(j, None) for j in rows_p if not m[j]],
                      key=lambda r: (r[0], -1 if r[1] is None else r[1]))
    if kind == "semi":
        return [(j,) for j in rows_p if m[j]]
    if kind == "anti":
        return [(j,) for j in rows_p if not m[j]]
    if not bkeys:                       # NOT IN an empty set: every row
        return [(j,) for j in rows_p]
    if any(_is_null(b) for b in bkeys):  # a NULL in the set: UNKNOWN
        return []
    return [(j,) for j in rows_p if not _is_null(pkeys[j]) and not m[j]]


@pytest.mark.parametrize("kind", JOIN_KINDS)
@pytest.mark.parametrize("case",
                         sorted(LOOKUP_CASES) + sorted(OPERATOR_CASES))
def test_join_operators_through_the_dense_index(case, kind):
    """The same cases through HashBuildOperator + LookupJoinOperator:
    the build publishes the index exactly where the span fits, the
    stand-alone probe kernels read it, and every join type answers what
    a nested loop answers (NOT IN with a NULL in the build included).
    OPERATOR_CASES are the keys the index cannot serve: each takes the
    tier its type and span pick, with the same answers."""
    from presto_tpu.batch import Batch, Dictionary, column_from_pylist
    from presto_tpu.exec.driver import Pipeline
    from presto_tpu.exec.joinop import (
        HashBuildOperatorFactory, LookupJoinOperatorFactory,
    )
    from presto_tpu.exec.operators import (
        OutputCollectorFactory, ValuesOperatorFactory,
    )
    from presto_tpu.exec.runner import execute_pipelines

    if case in OPERATOR_CASES:
        bkeys, pkeys, key_type, want_tier = OPERATOR_CASES[case]
    else:
        bkeys, pkeys = LOOKUP_CASES[case]
        key_type = T.BIGINT
        want_tier = "sorted" if case == "over_the_bound" else "dense"
    width = len((bkeys or pkeys)[0])
    schema = [key_type] * width + [T.BIGINT]   # keys..., row number
    chans = list(range(width))
    shared = [Dictionary(sorted({k[c] for k in bkeys + pkeys} - {None}))
              if t.is_dictionary else None
              for c, t in enumerate(schema[:width])] + [None]

    def batch(keys):
        rows = [k + (i,) for i, k in enumerate(keys)]
        return Batch(tuple(
            column_from_pylist(t, [r[c] for r in rows], shared[c])
            for c, t in enumerate(schema)), len(rows))

    build = HashBuildOperatorFactory(chans, schema)
    bp = Pipeline([
        ValuesOperatorFactory([batch(bkeys)] if bkeys else []),
        build], name="build")
    out = OutputCollectorFactory()
    pp = Pipeline([
        ValuesOperatorFactory([batch(pkeys)]),
        LookupJoinOperatorFactory(
            build, chans, schema,
            "anti" if kind == "notin" else kind,
            null_aware=(kind == "notin")),
        out], name="probe")
    task = execute_pipelines([bp, pp])
    if kind in ("inner", "left"):
        got = sorted(((r[width], r[2 * width + 1]) for r in out.rows()),
                     key=lambda r: (r[0], -1 if r[1] is None else r[1]))
    else:
        got = sorted((r[width],) for r in out.rows())
    assert got == _ref_join_rows(bkeys, pkeys, kind)
    tiers = {s.operator.split(".")[-1]: s.kernel_tier
             for s in task.operator_stats if s.kernel_tier}
    assert tiers == {"HashBuildOperator": want_tier,
                     "LookupJoinOperator": want_tier}


# ---------------------------------------------------------------------------
# filter / sort / hash
# ---------------------------------------------------------------------------

def test_selected_positions_exact():
    mask = jnp.asarray([True, False, True, True, False, True, False, False])
    idx, cnt = selected_positions(mask, None, jnp.asarray(6), 8)
    assert int(cnt) == 4
    assert np.asarray(idx)[:4].tolist() == [0, 2, 3, 5]
    valid = jnp.asarray([True, True, False, True, True, True, True, True])
    idx, cnt = selected_positions(mask, valid, jnp.asarray(6), 8)
    assert int(cnt) == 3
    assert np.asarray(idx)[:3].tolist() == [0, 3, 5]


def test_sort_permutation():
    vals = np.array([3.0, 1.0, 2.0, 0.0, 9.9], dtype=np.float64)
    valid = np.array([True, True, True, False, True])
    perm = sort_permutation(
        [(jnp.asarray(vals), jnp.asarray(valid), T.DOUBLE, False, False)],
        jnp.asarray(5))
    # ascending, nulls last: 1.0, 2.0, 3.0, 9.9, NULL
    assert np.asarray(perm).tolist() == [1, 2, 0, 4, 3]
    perm = sort_permutation(
        [(jnp.asarray(vals), jnp.asarray(valid), T.DOUBLE, True, True)],
        jnp.asarray(5))
    # descending, nulls first
    assert np.asarray(perm).tolist() == [3, 4, 0, 2, 1]


def test_sort_negative_floats_and_padding():
    vals = np.array([-1.5, 2.0, -3.0, 0.0, 99.0, 99.0], dtype=np.float64)
    perm = sort_permutation(
        [(jnp.asarray(vals), None, T.DOUBLE, False, False)],
        jnp.asarray(4))  # rows 4,5 are padding
    assert np.asarray(perm)[:4].tolist() == [2, 0, 3, 1]


def test_sort_multi_key():
    a = np.array([1, 2, 1, 2], dtype=np.int64)
    b = np.array([9, 8, 7, 6], dtype=np.int64)
    perm = sort_permutation(
        [(jnp.asarray(a), None, T.BIGINT, False, False),
         (jnp.asarray(b), None, T.BIGINT, True, False)],
        jnp.asarray(4))
    # a asc, b desc: (1,9),(1,7),(2,8),(2,6)
    assert np.asarray(perm).tolist() == [0, 2, 1, 3]


def test_row_hash_partitions():
    vals = jnp.asarray(np.arange(1000, dtype=np.int64))
    h = row_hash([(vals, None, T.BIGINT)])
    parts = np.asarray(partition_of(h, 8))
    # roughly balanced
    counts = np.bincount(parts, minlength=8)
    assert counts.min() > 80
    # deterministic
    h2 = row_hash([(vals, None, T.BIGINT)])
    assert np.array_equal(np.asarray(h), np.asarray(h2))


class TestDirectGroupby:
    """direct (mixed-radix + segment reduce) vs sort-based grouped
    aggregation must agree, including nullable keys and fused filters."""

    def _run_both(self, key_codes_np, key_valid_np, doms, vals_np,
                  vals_valid_np, live_np, n):
        import jax.numpy as jnp

        from presto_tpu import types as T
        from presto_tpu.ops.groupby import (
            decode_direct_keys, direct_grouped_aggregate, grouped_aggregate,
        )

        keys = [(jnp.asarray(c), None if v is None else jnp.asarray(v))
                for c, v in zip(key_codes_np, key_valid_np)]
        aggs = [("sum", jnp.asarray(vals_np),
                 None if vals_valid_np is None else jnp.asarray(vals_valid_np)),
                ("count", jnp.asarray(vals_np), None),
                ("min", jnp.asarray(vals_np), None),
                ("max", jnp.asarray(vals_np), None)]
        live = None if live_np is None else jnp.asarray(live_np)
        present, results = direct_grouped_aggregate(
            keys, doms, aggs, jnp.asarray(n), live_mask=live)
        slots = jnp.nonzero(present, size=present.shape[0], fill_value=0)[0]
        ngd = int(present.sum())
        decoded = decode_direct_keys(
            slots, [v is not None for v in key_valid_np], doms)
        direct = {}
        for i in range(ngd):
            key = tuple(
                None if (valid is not None and not bool(valid[i]))
                else int(codes[i]) for codes, valid in decoded)
            direct[key] = tuple(
                int(np.asarray(v)[slots[i]]) for v, _ in results)

        # sort path needs compacted live rows; emulate by masking via numpy
        mask = np.ones(len(vals_np), bool) if live_np is None else live_np.copy()
        mask &= np.arange(len(vals_np)) < n
        idx = np.nonzero(mask)[0]
        cap = max(1, 1 << int(np.ceil(np.log2(max(len(idx), 1)))))
        def padc(a, fill=0):
            out = np.full(cap, fill, dtype=np.asarray(a).dtype)
            out[:len(idx)] = np.asarray(a)[idx]
            return jnp.asarray(out)
        skeys = []
        for c, v in zip(key_codes_np, key_valid_np):
            skeys.append((padc(c), None if v is None else padc(v, False),
                          T.INTEGER))
        saggs = [("sum", padc(vals_np),
                  None if vals_valid_np is None else padc(vals_valid_np, False)),
                 ("count", padc(vals_np), None),
                 ("min", padc(vals_np), None),
                 ("max", padc(vals_np), None)]
        gi, ng, sres = grouped_aggregate(skeys, saggs, jnp.asarray(len(idx)),
                                         cap)
        ngs = int(ng)
        sorted_out = {}
        for i in range(ngs):
            row = int(np.asarray(gi)[i])
            key = []
            for c, v in zip(key_codes_np, key_valid_np):
                cc = padc(c); vv = None if v is None else padc(v, False)
                key.append(None if (vv is not None and not bool(np.asarray(vv)[row]))
                           else int(np.asarray(cc)[row]))
            sorted_out[tuple(key)] = tuple(
                int(np.asarray(v)[i]) for v, _ in sres)
        return direct, sorted_out

    def test_matches_sort_path_with_nulls_and_filter(self):
        rng = np.random.default_rng(5)
        n, cap = 900, 1024
        k1 = rng.integers(0, 5, cap).astype(np.int32)
        k1v = rng.random(cap) > 0.2
        k2 = rng.integers(0, 3, cap).astype(np.int32)
        vals = rng.integers(-100, 100, cap)
        vv = rng.random(cap) > 0.1
        live = rng.random(cap) > 0.3
        direct, sorted_out = self._run_both(
            [k1, k2], [k1v, None], [5, 3], vals, vv, live, n)
        assert direct == sorted_out
        assert len(direct) > 0

    def test_null_key_forms_one_group(self):
        k = np.zeros(8, np.int32)
        kv = np.array([True, False, True, False] * 2)
        vals = np.arange(8)
        direct, sorted_out = self._run_both(
            [k], [kv], [1], vals, None, None, 8)
        assert direct == sorted_out
        assert set(direct) == {(0,), (None,)}
