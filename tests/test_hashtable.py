"""The open-addressing table (ops/hashtable.py) against numpy oracles.

The XLA claim loop as the join lookup uses it: the 1-byte hash-prefix
reject, a table too small for its keys, and duplicate and missing probe
keys."""

import collections

import numpy as np


def _build_and_probe(build_keys, probe_keys, cap):
    """(ok, match count per probe key) through pages_hash_build /
    pages_hash_probe over one BIGINT key, no nulls."""
    import jax.numpy as jnp

    from presto_tpu import types as T
    from presto_tpu.ops import hashtable as H

    tw, tp, tu, starts, counts, _perm, _has_null, ok = H.pages_hash_build(
        [(jnp.asarray(build_keys), None, T.BIGINT)],
        jnp.asarray(len(build_keys)), cap)
    _lo, cnt, _live = H.pages_hash_probe(
        (tw, tp, tu, starts, counts),
        [(jnp.asarray(probe_keys), None, T.BIGINT)],
        jnp.asarray(len(probe_keys)))
    return bool(ok), np.asarray(cnt)


def test_pages_hash_full_table_reports_not_ok():
    """A table too small for its keys says so (the bounded claim loop
    gives up), which is what sends HashBuildOperator to a larger table
    or to the canonical path."""
    ok, _ = _build_and_probe(np.arange(1000), np.arange(8), 64)
    assert not ok


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
    both = slot.astype(np.int64) << 8 | prefix
    pairs, times = np.unique(both, return_counts=True)
    same = np.flatnonzero(both == pairs[times >= 2][0])[:2]
    ok, cnt = _build_and_probe(np.repeat(same, (8, 3)), same, 256)
    assert ok and cnt.tolist() == [8, 3]


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
