"""Composed multi-chip query steps: one jitted SPMD program per stage pair.

The reference executes a distributed aggregation as PARTIAL agg ->
PartitionedOutput -> exchange -> FINAL agg across worker processes
(HashAggregationOperator.Step:61; AddExchanges.java:114 chooses the
partitioning), and a distributed join as two co-hash-partitioned exchanges
feeding HashBuilder/LookupJoin per node (P1/P8 in SURVEY §2.13).  Here each
such stage pair is ONE ``shard_map``-ped, jitted XLA program over the mesh:
the exchange is an ``all_to_all`` in the middle of the program, so XLA can
overlap it with the surrounding compute — there is no serialized
"serialize page / HTTP / deserialize" hop to hide.

Inputs are global row-sharded arrays ([P*C] with dim 0 over the mesh axis)
plus a per-shard live-row count vector [P]; every column travels as
(values, valid) with an all-True valid standing in for "no nulls" so the
pytree structure is static.  Outputs are per-shard padded blocks [P*cap]
with per-shard counts and overflow flags; the host re-runs at a bigger
capacity bucket on overflow (the distributed rehash policy).
"""

from __future__ import annotations

from typing import Sequence

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from presto_tpu import kernelcache
from presto_tpu import types as T
from presto_tpu.ops import join as J
from presto_tpu.ops.groupby import grouped_aggregate
from presto_tpu.ops.hashing import partition_of, row_hash
from presto_tpu.parallel.exchange import broadcast_rows, repartition
from presto_tpu.parallel.mesh import AXIS


def _key_triples(vals, valids, types):
    return [(v, g, t) for v, g, t in zip(vals, valids, types)]


# Final-step merge of a partial aggregate, keyed by the partial's prim:
# count partials are summed; sum partials summed; min/max re-min/maxed.
_FINAL_PRIM = {"count": "sum", "sum": "sum", "min": "min", "max": "max"}


def make_partitioned_aggregate_step(
    key_types: Sequence[T.Type],
    agg_prims: Sequence[str],
    group_cap: int,
    slot_cap: int,
    out_cap: int,
    axis_name: str = AXIS,
):
    """Build the SPMD program for a full distributed GROUP BY:

        local PARTIAL agg -> all_to_all by key hash -> local FINAL agg

    Returned callable (to be jitted under the mesh) takes
    ``(key_vals [K][P*C], key_valids [K][P*C], agg_vals [A][P*C],
    agg_valids [A][P*C], num_rows [P])`` and returns
    ``(out_key_vals [K][P*out_cap], out_key_valids, out_agg_vals
    [A][P*out_cap], out_agg_cnts, num_groups [P], overflow [P])``.
    """
    key_types = list(key_types)
    agg_prims = list(agg_prims)

    def shard_fn(key_vals, key_valids, agg_vals, agg_valids, num_rows):
        n = num_rows[0]
        # ---- PARTIAL: local grouped aggregation --------------------------
        kcols = _key_triples(key_vals, key_valids, key_types)
        agg_ins = list(zip(agg_prims, agg_vals, agg_valids))
        gi, ng, partial = grouped_aggregate(kcols, agg_ins, n, group_cap)
        ng_cap = jnp.minimum(ng, group_cap)
        live = jnp.arange(group_cap) < ng_cap
        pk_vals = [v[gi] for v in key_vals]
        pk_valids = [g[gi] for g in key_valids]
        p_vals = [vals for vals, _ in partial]
        p_cnts = [cnt for _, cnt in partial]
        overflow = ng > group_cap

        # ---- EXCHANGE: co-locate equal keys by hash ----------------------
        h = row_hash(_key_triples(pk_vals, pk_valids, key_types))
        dest = partition_of(h, jax.lax.axis_size(axis_name))
        payload = pk_vals + pk_valids + p_vals + p_cnts
        recv, n_recv, ex_of = repartition(payload, live, dest, slot_cap,
                                          group_cap, axis_name)
        k = len(key_vals)
        a = len(agg_prims)
        rk_vals = recv[:k]
        rk_valids = recv[k:2 * k]
        r_vals = recv[2 * k:2 * k + a]
        r_cnts = recv[2 * k + a:]

        # ---- FINAL: merge partials per key -------------------------------
        fcols = _key_triples(rk_vals, rk_valids, key_types)
        f_ins = []
        for prim, v, c in zip(agg_prims, r_vals, r_cnts):
            if prim == "count":
                f_ins.append(("sum", v, None))
            else:
                f_ins.append((_FINAL_PRIM[prim], v, c > 0))
            f_ins.append(("sum", c.astype(jnp.int64), None))  # merge counts
        fgi, fng, final = grouped_aggregate(fcols, f_ins, n_recv, out_cap)
        out_k_vals = [v[fgi] for v in rk_vals]
        out_k_valids = [g[fgi] for g in rk_valids]
        out_vals, out_cnts = [], []
        for i, prim in enumerate(agg_prims):
            vals, _ = final[2 * i]
            cnts, _ = final[2 * i + 1]
            out_vals.append(vals)
            out_cnts.append(cnts)
        overflow = overflow | ex_of | (fng > out_cap)
        return (out_k_vals, out_k_valids, out_vals, out_cnts,
                fng.reshape(1), overflow.reshape(1))

    k = len(key_types)
    a = len(agg_prims)
    row = P(axis_name)
    in_specs = ([row] * k, [row] * k, [row] * a, [row] * a, row)
    out_specs = ([row] * k, [row] * k, [row] * a, [row] * a, row, row)
    return shard_fn, in_specs, out_specs


def make_partitioned_join_step(
    key_types: Sequence[T.Type],
    n_build_payload: int,
    n_probe_payload: int,
    slot_cap: int,
    local_cap: int,
    out_cap: int,
    axis_name: str = AXIS,
    broadcast_build: bool = False,
):
    """Build the SPMD program for a distributed inner hash join:

        all_to_all both sides by key hash (P1/P8)  -- or --
        all_gather the build side (P2, broadcast join)
        then local sorted-build join per shard.

    Returned callable takes
    ``(b_keys [K][P*C], b_key_valids, b_payload [Nb][P*C],
    p_keys [K][P*C], p_key_valids, p_payload [Np][P*C],
    n_build [P], n_probe [P])`` and returns
    ``(b_payload_out [Nb][P*out_cap], p_payload_out [Np][P*out_cap],
    total [P], overflow [P])`` — the joined rows, per shard.
    """
    key_types = list(key_types)
    nkeys = len(key_types)

    def shard_fn(b_keys, b_key_valids, b_payload,
                 p_keys, p_key_valids, p_payload, n_build, n_probe):
        nb, npr = n_build[0], n_probe[0]
        cap = b_keys[0].shape[0]
        pcap = p_keys[0].shape[0]
        of = jnp.zeros((), bool)

        if broadcast_build:
            bufs, nb, bof = broadcast_rows(
                list(b_keys) + list(b_key_valids) + list(b_payload),
                nb, local_cap, axis_name)
            of = of | bof
            b_keys = bufs[:nkeys]
            b_key_valids = bufs[nkeys:2 * nkeys]
            b_payload = bufs[2 * nkeys:]
        else:
            nparts = jax.lax.axis_size(axis_name)
            hb = row_hash(_key_triples(b_keys, b_key_valids, key_types))
            live_b = jnp.arange(cap) < nb
            bufs, nb, bof = repartition(
                list(b_keys) + list(b_key_valids) + list(b_payload),
                live_b, partition_of(hb, nparts), slot_cap, local_cap,
                axis_name)
            b_keys = bufs[:nkeys]
            b_key_valids = bufs[nkeys:2 * nkeys]
            b_payload = bufs[2 * nkeys:]
            hp = row_hash(_key_triples(p_keys, p_key_valids, key_types))
            live_p = jnp.arange(pcap) < npr
            pufs, npr, pof = repartition(
                list(p_keys) + list(p_key_valids) + list(p_payload),
                live_p, partition_of(hp, nparts), slot_cap, local_cap,
                axis_name)
            p_keys = pufs[:nkeys]
            p_key_valids = pufs[nkeys:2 * nkeys]
            p_payload = pufs[2 * nkeys:]
            of = of | bof | pof

        # ---- local sorted-build join ------------------------------------
        bcols = _key_triples(b_keys, b_key_valids, key_types)
        pcols = _key_triples(p_keys, p_key_valids, key_types)
        bids, pids = J.canonical_ids(bcols, pcols, nb, npr)
        sorted_b, perm_b = J.build_index(bids)
        lo, counts = J.probe_counts(sorted_b, perm_b, pids)
        probe_idx, build_idx, row_valid, _, total = J.expand_matches(
            lo, counts, perm_b, out_cap)
        b_out = [jnp.where(row_valid, a[build_idx], jnp.zeros((), a.dtype))
                 for a in b_payload]
        p_out = [jnp.where(row_valid, a[probe_idx], jnp.zeros((), a.dtype))
                 for a in p_payload]
        of = of | (total > out_cap)
        return (b_out, p_out,
                jnp.minimum(total, out_cap).astype(jnp.int64).reshape(1),
                of.reshape(1))

    row = P(axis_name)
    in_specs = ([row] * nkeys, [row] * nkeys, [row] * n_build_payload,
                [row] * nkeys, [row] * nkeys, [row] * n_probe_payload,
                row, row)
    out_specs = ([row] * n_build_payload, [row] * n_probe_payload, row, row)
    return shard_fn, in_specs, out_specs


def make_partitioned_topn_step(
    sort_types: Sequence[T.Type],
    descending: Sequence[bool],
    n_payload: int,
    limit: int,
    axis_name: str = AXIS,
):
    """Build the SPMD program for a distributed TopN (the mesh analogue
    of the sorted-merge exchange / MergeOperator.java:45 pattern):

        local sort + truncate to ``limit`` candidates per shard
        -> all_gather the candidate blocks over ICI
        -> final sort + truncate, replicated on every shard

    Returned callable takes ``(sort_vals [K][P*C], sort_valids
    [K][P*C], payload [Npay][P*C], num_rows [P])`` and returns
    ``(top_sort_vals [K][limit], top_sort_valids, top_payload
    [Npay][limit], count [])`` — identical (replicated) on every shard,
    so the out specs carry no mesh axis."""
    sort_types = list(sort_types)
    descending = list(descending)
    nkeys = len(sort_types)

    def shard_fn(s_vals, s_valids, payload, num_rows):
        from presto_tpu.ops.sort import sort_permutation

        n = num_rows[0]
        cap = s_vals[0].shape[0]
        keys = [(v, g, t, d, False)
                for v, g, t, d in zip(s_vals, s_valids, sort_types,
                                      descending)]
        perm = sort_permutation(keys, n)
        # per-shard candidate block: min(limit, cap) rows (a shard can
        # contribute at most cap rows; a limit above that is fine — the
        # union below still holds every possible top-limit row because
        # each shard keeps ITS best min(limit, cap))
        block = min(limit, cap)
        top = perm[:block].astype(jnp.int32)
        cand = jnp.minimum(n, block)
        cols = ([v[top] for v in s_vals] + [g[top] for g in s_valids]
                + [p[top] for p in payload])
        # broadcast exchange compacts the ragged candidate blocks into
        # the identical union on every shard (P2 primitive)
        nparts = jax.lax.axis_size(axis_name)
        gathered, total, _of = broadcast_rows(cols, cand,
                                              nparts * block, axis_name)
        g_svals = gathered[:nkeys]
        g_valids = gathered[nkeys:2 * nkeys]
        g_pay = gathered[2 * nkeys:]
        fkeys = [(v, g, t, d, False)
                 for v, g, t, d in zip(g_svals, g_valids, sort_types,
                                       descending)]
        fperm = sort_permutation(fkeys, total)[:limit].astype(jnp.int32)
        out_svals = [v[fperm] for v in g_svals]
        out_valids = [g[fperm] for g in g_valids]
        out_pay = [p[fperm] for p in g_pay]
        return (out_svals, out_valids, out_pay,
                jnp.minimum(total, limit))

    row = P(axis_name)
    rep = P()
    in_specs = ([row] * nkeys, [row] * nkeys, [row] * n_payload, row)
    out_specs = ([rep] * nkeys, [rep] * nkeys, [rep] * n_payload, rep)
    return shard_fn, in_specs, out_specs


def jit_step(mesh, shard_fn, in_specs, out_specs):
    """shard_map + jit a step built by one of the factories above."""
    mapped = jax.shard_map(shard_fn, mesh=mesh, in_specs=in_specs,
                           out_specs=out_specs, check_vma=False)
    return kernelcache.jit(mapped, "mesh_step")
