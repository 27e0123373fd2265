"""Compile the main path's kernels for a TPU v5e that is described, not
attached (on-chip-measurement guide, section 2).

These are compiles, not chip runs: the TPU compiler installed here raises
what the chip's compiler would raise (64-bit rewrites it cannot do,
kernels it refuses, programs that do not fit), at the shapes SF1 produces
behind ``scan_batch_rows`` = 65536.  Nothing executes, so they say nothing
about results or times — ``chip_smoke.py`` does that on the chip.

The trace-time branches that ask ``jax.default_backend()`` see the CPU in
such a compile; the ``tpu_backend`` fixture steers them here, in the test.
The topology is described inside a module-scoped fixture (only the xdist
worker that is handed this file loads libtpu), the compilation cache is
off around every compile (a described-device entry cannot be read back),
and no child process is started.
"""

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from presto_tpu import types as T

ROWS = 65536          # EngineConfig.scan_batch_rows
# The sort / claim-loop kernels compile in time proportional to their
# length (tens of seconds to minutes at SF1 sizes, measured here), so they
# are compiled at a quarter batch against reduced tables: the lowering the
# chip's compiler accepts or refuses is the same.
SMALL = 16384


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_compile_cache():
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture
def tpu_backend(monkeypatch):
    """Take the branches the chip takes (ops/keys.py f32 DOUBLE ordering,
    ops/groupby.py MXU einsum, ops/radix.py radix sort)."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


@pytest.fixture
def chip(one_chip, no_compile_cache, tpu_backend):
    """compile(fn, *specs) for one described v5e chip; spec(shape, dtype)
    builds the argument shapes placed on it."""

    class Chip:
        @staticmethod
        def spec(shape, dtype):
            shape = (shape,) if isinstance(shape, int) else shape
            return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

        @staticmethod
        def compile(fn, *specs):
            compiled = jax.jit(fn).lower(*specs).compile()
            assert compiled.memory_analysis() is not None
            return compiled

    return Chip


def test_hashtable_probe_insert(chip):
    """The claim loop of the device hash tables: int64 words, scatter-min
    claims, 16K rows into 256K slots."""
    from presto_tpu.ops.hashtable import probe_insert

    cap = 1 << 18
    chip.compile(
        lambda kw, live, tw, tp, tu: probe_insert([kw], live, (tw,), tp, tu),
        chip.spec(SMALL, jnp.int64), chip.spec(SMALL, bool),
        chip.spec(cap, jnp.int64), chip.spec(cap, jnp.uint8),
        chip.spec(cap, bool))


def test_pages_hash_build_and_probe(chip):
    """The hash-table join (exec/joinop.py takes it on the chip up to
    device_join_probe_max_build_rows = 128K): build 16K rows, probe 16K."""
    from presto_tpu.ops.hashtable import pages_hash_build, pages_hash_probe

    n_build, cap = SMALL, 2 * SMALL
    chip.compile(
        lambda k, v, n: pages_hash_build([(k, v, T.BIGINT)], n, cap),
        chip.spec(n_build, jnp.int64), chip.spec(n_build, bool),
        chip.spec((), jnp.int64))
    table = jax.eval_shape(
        lambda k, v, n: pages_hash_build([(k, v, T.BIGINT)], n, cap)[:5],
        jax.ShapeDtypeStruct((n_build,), jnp.int64),
        jax.ShapeDtypeStruct((n_build,), bool),
        jax.ShapeDtypeStruct((), jnp.int64))
    table = jax.tree.map(lambda s: chip.spec(s.shape, s.dtype), table)
    chip.compile(
        lambda t, k, v, n: pages_hash_probe(t, [(k, v, T.BIGINT)], n),
        table, chip.spec(SMALL, jnp.int64), chip.spec(SMALL, bool),
        chip.spec((), jnp.int64))


def test_radix_argsort(chip):
    """Radix sort replaces XLA sort on the chip (ops/radix.use_radix):
    one 64K batch of int64 keys."""
    from presto_tpu.ops.radix import radix_argsort_i64, use_radix

    assert use_radix()
    chip.compile(lambda w: radix_argsort_i64([w]),
                 chip.spec(ROWS, jnp.int64))


def test_join_build_index_and_probe_counts(chip):
    """The sorted join tier: build_index (radix on the chip) over a 16K
    build side, probe_counts of a 16K probe batch."""
    from presto_tpu.ops.join import build_index, probe_counts

    chip.compile(build_index, chip.spec(SMALL, jnp.int64))
    chip.compile(probe_counts, chip.spec(SMALL, jnp.int64),
                 chip.spec(SMALL, jnp.int32), chip.spec(SMALL, jnp.int64))


def test_join_dense_index_build_and_probe(chip):
    """The direct-address join tier at Q3's SF1 shapes: one worker's
    build of 128K rows over o_orderkey's span (1 << 23 slots), probed by
    a 64K batch.  The build's one sort is over int32 offsets (its
    compile, like every sort's, grows with the length: half a minute
    here); the probe is one row gather."""
    from presto_tpu.ops.join import build_dense_index, probe_dense

    size = 1 << 23
    built = chip.compile(lambda ids: build_dense_index(ids, size),
                         chip.spec(2 * ROWS, jnp.int64))
    # the index and the perm, and nothing else, leave the program
    assert built.memory_analysis().output_size_in_bytes < 8 * size + (1 << 20)
    chip.compile(probe_dense, chip.spec((size, 2), jnp.int32),
                 chip.spec(ROWS, jnp.int64))


def _q1_aggs(values, valid=None):
    return [("sum", values, valid), ("sum", values, valid),
            ("count", None, None)]


def test_direct_groupby_mxu(chip):
    """TPC-H Q1's shape: two dictionary keys (3 x 2 codes), DOUBLE sums —
    the blocked one-hot einsum with the hi/lo f32 split."""
    from presto_tpu.ops.groupby import direct_grouped_aggregate

    compiled = chip.compile(
        lambda k0, k1, v, n: direct_grouped_aggregate(
            [(k0, None), (k1, None)], [3, 2], _q1_aggs(v), n),
        chip.spec(ROWS, jnp.int32), chip.spec(ROWS, jnp.int32),
        chip.spec(ROWS, jnp.float64), chip.spec((), jnp.int64))
    # the MXU branch, not the scatter: a convolution/dot is in the program
    text = compiled.as_text()
    assert "convolution" in text or " dot(" in text


def test_segment_pre_reduce_direct_and_sorted(chip):
    """The fused scan segment's partial aggregation (exec/fusion.py): the
    direct path over dictionary keys (one 64K batch) and the sort path
    over a BIGINT key (Q3's l_orderkey, 16K rows)."""
    from presto_tpu.ops.groupby import segment_pre_reduce

    f64 = np.dtype("float64")
    chip.compile(
        lambda k0, k1, v, lm, n: segment_pre_reduce(
            [(k0, None, T.VARCHAR), (k1, None, T.VARCHAR)], _q1_aggs(v),
            [f64, f64, np.dtype("int64")], n, lm, [3, 2], ROWS),
        chip.spec(ROWS, jnp.int32), chip.spec(ROWS, jnp.int32),
        chip.spec(ROWS, jnp.float64), chip.spec(ROWS, bool),
        chip.spec((), jnp.int64))
    chip.compile(
        lambda k, v, lm, n: segment_pre_reduce(
            [(k, None, T.BIGINT)], _q1_aggs(v),
            [f64, f64, np.dtype("int64")], n, lm, None, SMALL),
        chip.spec(SMALL, jnp.int64), chip.spec(SMALL, jnp.float64),
        chip.spec(SMALL, bool), chip.spec((), jnp.int64))


def _fused_ops(compiled):
    """The last word of every fusion's op_name in a compiled program."""
    return [line.split('op_name="')[1].split('"')[0].rsplit("/", 1)[-1]
            for line in compiled.as_text().splitlines()
            if " fusion(" in line and 'op_name="' in line]


def test_grouped_finish_direct(chip):
    """``HashAggregationOperator``'s finish on the direct tier
    (``groupby_direct``, ops/groupby.py) at the shape of TPC-H Q1's final
    step: 1,024 staged rows, two dictionary keys (domains 3 and 2 in
    their buckets, one nullable), DOUBLE sums, a count and a min over a
    dictionary column through its rank tables.  The present slots are
    compacted through ops/filter.py: no scatter-add (the ``bincount`` of
    the eager ``jnp.nonzero`` it replaces was the unnamed
    ``jit_scatter-add`` of the ledger's PR 40 line; an integer sum would
    keep ``direct_grouped_aggregate``'s exact one)."""
    from presto_tpu.ops.groupby import grouped_finish_kernel

    rows = 1024
    f64, i64, i32 = (np.dtype(d) for d in ("float64", "int64", "int32"))
    kernel = grouped_finish_kernel(
        (T.VARCHAR, T.VARCHAR), ("sum", "sum", "count", "min"),
        (f64, f64, i64, i32), (4, 2), rows)
    codes, real, mask = (chip.spec(rows, d)
                         for d in (jnp.int32, jnp.float64, bool))
    table = chip.spec(8, jnp.int32)
    ops = _fused_ops(chip.compile(
        kernel, (codes, codes), (mask, None), (real, real, None, codes),
        (mask, mask, None, mask), (None, None, None, (table, table)),
        chip.spec((), jnp.int32)))
    assert "scatter-add" not in ops and "scatter" in ops


def test_order_by_finish(chip):
    """``OrderByOperator``'s finish (``order_by``, ops/sort.py) at the
    shape of TPC-H Q1's: 1,024 staged rows ordered by two dictionary
    keys through their rank tables (radix passes on the chip), ten
    columns gathered through the permutation."""
    from presto_tpu.ops.sort import _columns_kernel

    rows = 1024
    kernel = _columns_kernel(
        ((0, T.VARCHAR, False, False), (1, T.VARCHAR, False, False)),
        True, rows)
    codes, real, mask = (chip.spec(rows, d)
                         for d in (jnp.int32, jnp.float64, bool))
    table = chip.spec(8, jnp.int32)
    chip.compile(
        kernel, ((codes, None), (codes, mask)) + ((real, mask),) * 8,
        (table, table), chip.spec((), jnp.int32))


@pytest.mark.parametrize("form", ["compacted", "left_for_the_sink"])
def test_end_of_a_filter_segment(chip, form):
    """The end of TPC-H Q3's lineitem segment at SF1 (exec/fusion.py
    ``_compile``): one 64K batch of an int64 key, two DOUBLEs and a date
    under a filter mask, then the partition ids.  Compacted through
    ops/filter.py: one int32 scatter, no scatter-add (``jnp.nonzero``'s
    was int64, ``fusion.7`` of PERF.md, PR 37).  Left for the sink that
    cuts rows on the host: no scatter and no gather at all."""
    from presto_tpu.ops.filter import selected_positions
    from presto_tpu.ops.hashing import partition_of, row_hash

    def kernel(okey, price, disc, ship, num_rows):
        cols = (okey, price, disc, ship)
        mask = ship > 9204
        if form == "compacted":
            idx, count = selected_positions(mask, None, num_rows, ROWS)
            cols = tuple(v[idx] for v in cols)
        else:
            live = (jnp.arange(ROWS) < num_rows) & mask
            count = live.sum()
        parts = partition_of(row_hash([(cols[0], None, T.BIGINT)]), 2)
        if form != "compacted":
            parts = jnp.where(live, parts, 2)
        return cols, count, parts

    ops = _fused_ops(chip.compile(
        kernel, chip.spec(ROWS, jnp.int64), chip.spec(ROWS, jnp.float64),
        chip.spec(ROWS, jnp.float64), chip.spec(ROWS, jnp.int32),
        chip.spec((), jnp.int64)))
    assert "scatter-add" not in ops
    if form == "compacted":
        assert "scatter" in ops and "gather" in ops
    else:
        assert not {"scatter", "gather"} & set(ops), sorted(set(ops))


def test_merge_of_held_partials(chip):
    """The once-a-task merge of the partial states a segment held on the
    device (exec/fusion.py, ops/groupby.merge_pre_reduced) at TPC-H Q1's
    SF1 shape: 46 partials padded to 64, each the direct path's six-slot
    domain of two dictionary keys, DOUBLE sums, a count state (BIGINT,
    summed exactly) and a DOUBLE min."""
    from presto_tpu.ops.groupby import merge_pre_reduced

    f64, i64 = np.dtype("float64"), np.dtype("int64")
    k, domain = 64, 6
    stacked = (
        (chip.spec((k, domain), jnp.int32), None),
        (chip.spec((k, domain), jnp.int32), None),
        (chip.spec((k, domain), jnp.float64), chip.spec((k, domain), bool)),
        (chip.spec((k, domain), jnp.int64), None),
        (chip.spec((k, domain), jnp.float64), chip.spec((k, domain), bool)))
    chip.compile(
        lambda cols, counts: merge_pre_reduced(
            cols, counts, [T.VARCHAR, T.VARCHAR], [3, 2],
            ["sum", "sum", "min"], [f64, i64, f64]),
        stacked, chip.spec(k, jnp.int64))


def test_run_program_over_a_kept_run(chip):
    """The program that pre-reduces a whole kept run in one launch
    (exec/fusion.py ``fused_segment_run``) at TPC-H Q1's SF1 shape: the
    direct pre-reduce of one 64K batch mapped (``lax.map``) over a run of
    46 batches stacked to 64 (exec/scancache.py), each batch read at a
    traced index and its row count from the run's ``int32[64]``, then the
    merge of the 64 partials in the same program."""
    from jax import lax

    from presto_tpu.ops.groupby import merge_pre_reduced, segment_pre_reduce

    f64, i64 = np.dtype("float64"), np.dtype("int64")
    n_pad = 64

    def run(k0, k1, v, live, num_rows, start):
        def one(i):
            b = jnp.minimum(start + i, n_pad - 1)
            at = lambda a: lax.dynamic_index_in_dim(a, b, keepdims=False)
            key_outs, agg_outs, count = segment_pre_reduce(
                [(at(k0), None, T.VARCHAR), (at(k1), None, T.VARCHAR)],
                _q1_aggs(at(v)), [f64, f64, i64], num_rows[b], at(live),
                [3, 2], ROWS)
            return tuple(key_outs) + tuple(agg_outs), count

        outs, counts = lax.map(one, jnp.arange(n_pad))
        return merge_pre_reduced(outs, counts, [T.VARCHAR, T.VARCHAR],
                                 [3, 2], ["sum", "sum", "sum"],
                                 [f64, f64, i64])

    chip.compile(
        run, chip.spec((n_pad, ROWS), jnp.int32),
        chip.spec((n_pad, ROWS), jnp.int32),
        chip.spec((n_pad, ROWS), jnp.float64),
        chip.spec((n_pad, ROWS), bool), chip.spec(n_pad, jnp.int32),
        chip.spec((), jnp.int64))


def test_grouped_aggregate_sort_tier(chip):
    """The program that serves every unbounded GROUP BY (ops/groupby.py
    ``grouped_aggregate``: radix sort, run boundaries, segment reduce):
    a 16K batch, BIGINT key, DOUBLE sum + count, a group a row."""
    from presto_tpu.ops.groupby import grouped_aggregate

    chip.compile(
        lambda k, v, n: grouped_aggregate(
            [(k, None, T.BIGINT)],
            [("sum", v, None), ("count", v, None)], n, SMALL),
        chip.spec(SMALL, jnp.int64), chip.spec(SMALL, jnp.float64),
        chip.spec((), jnp.int64))


def test_double_keys_take_the_f32_ordering(chip):
    """DOUBLE sort/group keys: the chip cannot bitcast f64 (the X64 rewrite
    refuses it), so ops/keys.py must take its f32-pattern branch there."""
    from presto_tpu.ops.keys import to_sortable_i64

    chip.compile(lambda v: to_sortable_i64(jnp, v, T.DOUBLE),
                 chip.spec(ROWS, jnp.float64))


def test_window_segmented_scan_and_rank(chip):
    """Window functions over one partition-sorted 64K batch: the segmented
    cumulative DOUBLE sum (associative_scan) and the ranking functions over
    ops/window._seg_bounds.  With int64 row indices _seg_bounds took 216 s
    to compile here and W.rank crashed the TPU compiler (PR 25 finding);
    it indexes in int32 now."""
    from presto_tpu.ops import window as W

    chip.compile(lambda seg, peer, v: (W._seg_cumsum(seg, v),
                                       W.rank(seg, peer),
                                       W.row_number(seg)),
                 chip.spec(ROWS, jnp.int32), chip.spec(ROWS, jnp.int32),
                 chip.spec(ROWS, jnp.float64))


def test_device_concat_append(chip):
    """exec/operator.device_concat keeps device batches on the device: one
    append program per (output bucket, input bucket) pair, here a join's
    64K-capacity output into a 16K bucket and into a 1M one, over the
    64-bit column types (BIGINT, DOUBLE with a validity mask); the first
    append of a concat makes the zeroed bucket inside the program."""
    from presto_tpu.exec.operator import _append_kernel, _first_append_kernel

    ins = ((chip.spec(ROWS, jnp.int64), None),
           (chip.spec(ROWS, jnp.float64), chip.spec(ROWS, bool)))
    for out_rows in (SMALL, 1 << 20):
        chip.compile(
            _append_kernel,
            ((chip.spec(out_rows, jnp.int64), None),
             (chip.spec(out_rows, jnp.float64), chip.spec(out_rows, bool))),
            ins, chip.spec((), jnp.int32), chip.spec((), jnp.int32))
        chip.compile(_first_append_kernel(out_rows), None, ins,
                     chip.spec((), jnp.int32), chip.spec((), jnp.int32))

