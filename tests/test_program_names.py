"""Every jitted program has a stable name (kernelcache.jit,
kernelcache.PROGRAM_NAMES): a device trace says ``jit_join_probe(...)``,
not ``jit_kernel(...)``."""

import ast
import os
import re

import jax
import jax.monitoring
import jax.numpy as jnp
import pytest

from presto_tpu import kernelcache
from tpch_queries import QUERIES

PACKAGE = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "presto_tpu")


def _trees():
    for folder, _dirs, files in os.walk(PACKAGE):
        for file in files:
            if file.endswith(".py"):
                path = os.path.join(folder, file)
                with open(path, encoding="utf-8") as f:
                    yield (os.path.relpath(path, PACKAGE),
                           ast.parse(f.read(), path))


def _is_helper(node):
    """``kernelcache.jit`` / ``_kc.jit`` as an expression."""
    return (isinstance(node, ast.Attribute) and node.attr == "jit"
            and isinstance(node.value, ast.Name)
            and node.value.id in ("kernelcache", "_kc"))


def test_no_jax_jit_outside_the_helper():
    offenders = []
    for path, tree in _trees():
        if path == "kernelcache.py":
            continue
        for node in ast.walk(tree):
            direct = (isinstance(node, ast.Attribute) and node.attr == "jit"
                      and isinstance(node.value, ast.Name)
                      and node.value.id == "jax")
            imported = (isinstance(node, ast.ImportFrom)
                        and (node.module or "").split(".")[0] == "jax"
                        and any(a.name == "jit" for a in node.names))
            if direct or imported:
                offenders.append(f"{path}:{node.lineno}")
    assert offenders == []


def test_every_name_at_a_call_site_is_in_the_closed_list():
    used, unnamed, picked = set(), [], []
    for path, tree in _trees():
        for node in ast.walk(tree):
            if not isinstance(node, ast.Call):
                continue
            if _is_helper(node.func):               # jit(fn, "name", ...)
                args = node.args[1:]
            elif node.args and _is_helper(node.args[0]):
                args = []                           # partial(jit, name=...)
            else:
                continue
            names = [a for a in args[:1]] + [
                k.value for k in node.keywords if k.arg == "name"]
            if len(names) == 1 and isinstance(names[0], ast.Constant):
                used.add(names[0].value)
            elif len(names) == 1 and isinstance(names[0], ast.Name):
                picked.append((path, tree, node))
            else:       # a name computed at run time could be per query
                unnamed.append(f"{path}:{node.lineno}")
    # a name picked at run time is picked from a table of constants
    for path, tree, node in picked:
        tables = [n.value for n in tree.body
                  if isinstance(n, ast.Assign)
                  and isinstance(n.value, ast.Dict)
                  and n.targets[0].id.endswith("_PROGRAM")]
        assert len(tables) == 1, path
        assert all(isinstance(v, ast.Constant) for v in tables[0].values)
        used.update(v.value for v in tables[0].values)
    assert unnamed == []
    assert used == set(kernelcache.PROGRAM_NAMES)


def test_names_are_one_per_kind_of_program():
    names = kernelcache.PROGRAM_NAMES
    assert len(set(names)) == len(names)
    assert all(re.fullmatch(r"[a-z][a-z0-9_]*", n) for n in names)
    assert "kernel" not in names


@pytest.mark.parametrize("name", ["join_probe", "fused_segment", "sort"])
def test_jit_names_the_xla_module(name):
    def kernel(x):
        return x * 2 + 1

    program = kernelcache.jit(kernel, name)
    assert kernel.__name__ == kernel.__qualname__ == name
    assert f"@jit_{name}" in program.lower(jnp.arange(4)).as_text()
    assert int(program(jnp.arange(4))[3]) == 7


def test_jit_passes_its_options_through():
    def kernel(x, *, k):
        return x * k

    program = kernelcache.jit(kernel, "sort", static_argnames=("k",))
    assert int(program(jnp.arange(3), k=5)[2]) == 10


def test_jit_refuses_a_name_outside_the_list():
    with pytest.raises(ValueError):
        kernelcache.jit(lambda x: x, "q3_probe_for_this_query")


class _Builds:
    """fun_name of every backend-compile event while it listens."""

    def __init__(self):
        self.names = []
        self.on = True
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, _secs, **kw):
        if self.on and event.endswith("backend_compile_duration"):
            self.names.append(str(kw.get("fun_name", "?")))


@pytest.mark.parametrize("number", [1, 3])
def test_served_queries_build_no_program_called_kernel(number):
    from presto_tpu.server.dqr import DistributedQueryRunner

    # fresh kernel-cache keys are not needed: a cached program was built
    # under its name too; what matters is what gets built from here on
    builds = _Builds()
    try:
        with DistributedQueryRunner.tpch(scale=0.01, n_workers=2) as dqr:
            dqr.execute(QUERIES[number])
    finally:
        builds.on = False
    anonymous = [n for n in builds.names
                 if n in ("kernel", "jit(kernel)") or "<lambda>" in n]
    assert anonymous == []
