"""The open-addressing table (ops/hashtable.py) against numpy oracles.

The hash tier's XLA claim loop, as the group-by and the join lookup use
it: collision storms, the rehash boundary (including the min/max identity
carry), null keys, the 1-byte hash-prefix reject, a full table, and
duplicate and missing probe keys."""

import collections

import numpy as np
import pytest


def _groupby_oracle(keys, valid, vals):
    ref_sum = collections.defaultdict(float)
    ref_cnt = collections.defaultdict(int)
    for i, k in enumerate(keys):
        kk = int(k) if (valid is None or valid[i]) else None
        ref_sum[kk] += float(vals[i])
        ref_cnt[kk] += 1
    return ref_sum, ref_cnt


def _extract_map(state):
    from presto_tpu.ops import hashtable as H

    n, key_outs, agg_outs = H.groupby_extract(state)
    n = int(n)
    kv, kvalid = key_outs[0]
    kv = np.asarray(kv)[:n]
    kb = (np.ones(n, bool) if kvalid is None
          else np.asarray(kvalid)[:n])
    out = {}
    for i in range(n):
        kk = int(kv[i]) if kb[i] else None
        out[kk] = tuple(float(np.asarray(acc)[:n][i])
                        for acc, _nn in agg_outs)
    return n, out


def test_hash_groupby_collision_storm():
    """Thousands of distinct keys crammed against a table at exactly 2x
    occupancy: every insert round contends, chains grow, and the result
    must still match numpy group-by exactly."""
    import jax.numpy as jnp

    from presto_tpu import types as T
    from presto_tpu.ops import hashtable as H

    rng = np.random.default_rng(7)
    n = 8192
    keys = rng.integers(0, 4096, n)          # ~4096 groups in 8192 slots
    vals = rng.uniform(-100, 100, n)
    state = H.groupby_init(8192, 2, [np.dtype(np.int64)], [True],
                           [("sum", np.dtype(np.float64)),
                            ("count", None)])
    state, ng, ok = H.groupby_update(
        state, [(jnp.asarray(keys), None, T.BIGINT)],
        [("sum", jnp.asarray(vals), None), ("count", None, None)],
        jnp.asarray(n))
    assert bool(ok)
    ref_sum, ref_cnt = _groupby_oracle(keys, None, vals)
    got_n, got = _extract_map(state)
    assert got_n == int(ng) == len(ref_sum)
    for kk, s in ref_sum.items():
        assert got[kk][0] == pytest.approx(s, rel=1e-9, abs=1e-7)
        assert got[kk][1] == ref_cnt[kk]


def test_hash_groupby_null_keys_form_one_group():
    import jax.numpy as jnp

    from presto_tpu import types as T
    from presto_tpu.ops import hashtable as H

    rng = np.random.default_rng(3)
    n = 4096
    keys = rng.integers(0, 64, n)
    valid = rng.random(n) > 0.3              # lots of null keys
    vals = np.ones(n)
    state = H.groupby_init(1024, 2, [np.dtype(np.int64)], [True],
                           [("sum", np.dtype(np.float64))])
    state, ng, ok = H.groupby_update(
        state, [(jnp.asarray(keys), jnp.asarray(valid), T.BIGINT)],
        [("sum", jnp.asarray(vals), None)], jnp.asarray(n))
    assert bool(ok)
    ref_sum, _ = _groupby_oracle(keys, valid, vals)
    got_n, got = _extract_map(state)
    assert got_n == len(ref_sum)             # null key = exactly 1 group
    assert got[None][0] == pytest.approx(ref_sum[None])


def test_hash_groupby_rehash_boundary_carries_minmax_identities():
    """Cross the rehash boundary mid-stream: groups inserted BEFORE the
    rehash carry their accumulated state; groups first installed AFTER
    it must land on identity-initialized min/max cells (regression: a
    zero-initialized cell folded min(0, x) = 0)."""
    import jax.numpy as jnp

    from presto_tpu import types as T
    from presto_tpu.ops import hashtable as H

    n = 2048
    keys1 = np.arange(n) % 400               # groups 0..399
    vals1 = np.arange(n, dtype=np.float64) + 100.0
    state = H.groupby_init(1024, 2, [np.dtype(np.int64)], [True],
                           [("min", np.dtype(np.float64)),
                            ("max", np.dtype(np.float64))])
    kc = [(jnp.asarray(keys1), None, T.BIGINT)]
    ag = [("min", jnp.asarray(vals1), None),
          ("max", jnp.asarray(vals1), None)]
    state, ng, ok = H.groupby_update(state, kc, ag, jnp.asarray(n))
    assert bool(ok) and int(ng) == 400
    state, ok = H.groupby_rehash(state, 4096, ["min", "max"])
    assert bool(ok)
    # batch 2: 400 NEW groups, values strictly positive
    keys2 = 1000 + (np.arange(n) % 400)
    vals2 = np.arange(n, dtype=np.float64) + 500.0
    state, ng, ok = H.groupby_update(
        state, [(jnp.asarray(keys2), None, T.BIGINT)],
        [("min", jnp.asarray(vals2), None),
         ("max", jnp.asarray(vals2), None)], jnp.asarray(n))
    assert bool(ok) and int(ng) == 800
    ref_min = collections.defaultdict(lambda: np.inf)
    ref_max = collections.defaultdict(lambda: -np.inf)
    for k, v in zip(keys1, vals1):
        ref_min[int(k)] = min(ref_min[int(k)], v)
        ref_max[int(k)] = max(ref_max[int(k)], v)
    for k, v in zip(keys2, vals2):
        ref_min[int(k)] = min(ref_min[int(k)], v)
        ref_max[int(k)] = max(ref_max[int(k)], v)
    got_n, got = _extract_map(state)
    assert got_n == 800
    for kk in ref_min:
        assert got[kk][0] == ref_min[kk], kk   # no stale zeros
        assert got[kk][1] == ref_max[kk], kk


def test_hash_insert_full_table_reports_not_ok_and_accumulates_nothing():
    """The rehash-boundary contract: when placement fails, ok=False and
    NO aggregation state changed, so rehash-and-retry is exactly-once."""
    import jax.numpy as jnp

    from presto_tpu import types as T
    from presto_tpu.ops import hashtable as H

    state = H.groupby_init(64, 2, [np.dtype(np.int64)], [True],
                           [("sum", np.dtype(np.float64))])
    keys = np.arange(1000)
    state2, ng, ok = H.groupby_update(
        state, [(jnp.asarray(keys), None, T.BIGINT)],
        [("sum", jnp.asarray(np.ones(1000)), None)], jnp.asarray(1000))
    assert not bool(ok)
    assert float(np.asarray(state2[4][0][0]).sum()) == 0.0


def test_hash_prefix_reject_byte_is_slot_independent():
    """The reject byte must come from hash bits the slot index does not
    use (PagesHash.java:49): keys colliding on the slot still disagree
    on the prefix almost always, so occupied-slot walks reject on one
    byte; and prefix-EQUAL colliding keys must still compare words."""
    import jax.numpy as jnp

    from presto_tpu.ops import hashtable as H

    h = H.hash_words([jnp.asarray(np.arange(1 << 14, dtype=np.int64))])
    slot, prefix = H.slot_and_prefix(h, 256)
    slot = np.asarray(slot)
    prefix = np.asarray(prefix)
    # per slot, prefixes of colliding keys are spread (not a function
    # of the slot): at 64 keys/slot expect ~56 distinct prefix values
    for s in (0, 17, 255):
        ps = prefix[slot == s]
        assert len(ps) > 0
        assert len(np.unique(ps)) > len(ps) // 2
    # correctness under engineered prefix collisions: keys with EQUAL
    # slot and EQUAL prefix must not alias (full word compare decides)
    h_np = np.asarray(h)
    pool = np.arange(1 << 14)
    same = pool[(slot == slot[0]) & (prefix == prefix[0])]
    if len(same) >= 2:
        from presto_tpu import types as T

        keys = np.repeat(same[:2], 8).astype(np.int64)
        state = H.groupby_init(256, 2, [np.dtype(np.int64)], [True],
                               [("count", None)])
        state, ng, ok = H.groupby_update(
            state, [(jnp.asarray(keys), None, T.BIGINT)],
            [("count", None, None)], jnp.asarray(len(keys)))
        assert bool(ok) and int(ng) == 2


def test_pages_hash_duplicate_and_missing_probe_keys():
    import jax.numpy as jnp

    from presto_tpu import types as T
    from presto_tpu.ops import hashtable as H

    rng = np.random.default_rng(11)
    bk = rng.integers(0, 300, 1024)
    bvalid = rng.random(1024) > 0.1
    pk = rng.integers(0, 600, 2048)
    pvalid = rng.random(2048) > 0.1
    table = H.pages_hash_build(
        [(jnp.asarray(bk), jnp.asarray(bvalid), T.BIGINT)],
        jnp.asarray(1000), 2048)
    tw, tp, tu, starts, counts, perm, has_null, ok = table
    assert bool(ok) and bool(has_null)
    lo, cnt, live = H.pages_hash_probe(
        (tw, tp, tu, starts, counts),
        [(jnp.asarray(pk), jnp.asarray(pvalid), T.BIGINT)],
        jnp.asarray(2048))
    lo, cnt = np.asarray(lo), np.asarray(cnt)
    perm_np = np.asarray(perm)
    ref = collections.Counter(
        int(k) for k, v in zip(bk[:1000], bvalid[:1000]) if v)
    for i in range(2048):
        want = ref.get(int(pk[i]), 0) if pvalid[i] else 0
        assert cnt[i] == want, i
        for j in range(cnt[i]):
            assert bk[perm_np[lo[i] + j]] == pk[i]
