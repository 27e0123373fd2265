"""The compaction helper (ops/filter.py ``selected_positions``): what
``jnp.nonzero(live, size=..., fill_value=0)`` returns, computed in int32
with one unique-index scatter, and the one form of it in the tree."""

import inspect

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from presto_tpu.ops.filter import selected_positions


def _want(live: np.ndarray, out_capacity: int):
    """(indices, count) by numpy: the live positions ascending, cut or
    zero-filled to ``out_capacity``."""
    pos = np.nonzero(live)[0][:out_capacity]
    idx = np.zeros(out_capacity, np.int64)
    idx[:len(pos)] = pos
    return idx, int(live.sum())


def _case(kind: str, cap: int):
    """(mask, valid, num_rows) of one mask shape at one capacity."""
    rng = np.random.default_rng(cap * 31 + len(kind))
    mask = rng.random(cap) < 0.4
    valid, num_rows = None, cap
    if kind == "all_false":
        mask = np.zeros(cap, bool)
    elif kind == "all_true":
        mask = np.ones(cap, bool)
    elif kind == "padded":          # rows past num_rows are padding
        num_rows = cap // 2
    elif kind == "valid":           # a NULL predicate selects nothing
        valid = rng.random(cap) < 0.7
        num_rows = cap - cap // 8
    return mask, valid, num_rows


@pytest.mark.parametrize("cap", [1, 4096, 65536])
@pytest.mark.parametrize("kind", ["random", "all_false", "all_true",
                                  "padded", "valid"])
def test_selected_positions_matches_nonzero(kind, cap):
    mask, valid, num_rows = _case(kind, cap)
    live = mask & (np.arange(cap) < num_rows)
    if valid is not None:
        live = live & valid
    idx, count = jax.jit(selected_positions, static_argnums=3)(
        jnp.asarray(mask), None if valid is None else jnp.asarray(valid),
        num_rows, cap)
    want_idx, want_count = _want(live, cap)
    assert idx.dtype == jnp.int32 and idx.shape == (cap,)
    np.testing.assert_array_equal(np.asarray(idx), want_idx)
    assert int(count) == want_count
    # and what it replaced
    np.testing.assert_array_equal(
        np.asarray(idx),
        np.asarray(jnp.nonzero(jnp.asarray(live), size=cap,
                               fill_value=0)[0]))


@pytest.mark.parametrize("out_capacity", [64, 4096, 8192])
def test_selected_positions_at_another_output_capacity(out_capacity):
    """A smaller output keeps the first positions (the count still says
    how many were live), a larger one zero-fills."""
    mask, _valid, num_rows = _case("random", 4096)
    idx, count = selected_positions(jnp.asarray(mask), None, num_rows,
                                    out_capacity)
    want_idx, want_count = _want(mask, out_capacity)
    np.testing.assert_array_equal(np.asarray(idx), want_idx)
    assert int(count) == want_count


def _primitives(jaxpr, out):
    """(primitive name, output dtypes) of every equation, nested ones
    included."""
    for eqn in jaxpr.eqns:
        out.append((eqn.primitive.name,
                    tuple(str(v.aval.dtype) for v in eqn.outvars)))
        for param in eqn.params.values():
            for sub in (param if isinstance(param, (list, tuple))
                        else [param]):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    _primitives(inner, out)
    return out


@pytest.mark.parametrize("with_valid", [False, True])
def test_selected_positions_scatters_once_in_int32(with_valid):
    """No scatter-add, nothing scattered in 64 bits: the chip ran
    nonzero's int64 scatter-add over repeated indices at 4.5 ms a
    65,536-row launch."""
    cap = 65536
    valid = jnp.ones(cap, bool) if with_valid else None
    prims = _primitives(jax.make_jaxpr(
        lambda m, v, n: selected_positions(m, v, n, cap))(
            jnp.ones(cap, bool), valid, jnp.int64(cap)).jaxpr, [])
    scatters = [(name, dtypes) for name, dtypes in prims
                if name.startswith("scatter")]
    assert scatters == [("scatter", ("int32",))], scatters
    assert not [p for p in prims if p[0] in ("sort", "while")]


def test_the_compaction_exists_once():
    """``segment_pre_reduce``'s direct path and every operator that
    compacts go through the helper; ``jnp.nonzero`` is gone from it."""
    from presto_tpu.ops import filter as F, groupby as G

    assert "nonzero(" not in inspect.getsource(F.selected_positions) \
        .split('"""')[2]
    pre_reduce = inspect.getsource(G.segment_pre_reduce)
    assert "selected_positions(" in pre_reduce
    assert "cumsum" not in pre_reduce and "nonzero" not in pre_reduce
