"""How the traced slice is planned (``run.slice_plan``) and that it holds
whole statements: the short branch as it always was, to the float; the long
branch through the window's own generator and tracer at a hundredth of real
time (the profiler left out).  ``test_rehearsal.py``'s traced join cell takes
the long branch on the CPU."""

import random
import time
import types

import pytest

from benchmark import run

SEED = 2 ** 31 + 4242


def parent_rule(cycle, seconds):
    """The slice as ``run_cell`` planned it for every cell before the long
    branch: what the short branch must return."""
    return (min(1.0, 0.1 * seconds),
            min(max(3.0, 3.0 * cycle + 0.5), 0.8 * seconds))


# the accepted cells' warm cycles at 51 s (scan-agg, join, mesh), and the
# traced CPU rehearsal of the mesh cell at SF0.01 (a repeat q1 on four
# virtual devices: 0.03 to 0.1 s) in its 2 s window
@pytest.mark.parametrize("walls, seconds", [
    ([0.11, 0.05], 51.0), ([1.95], 51.0), ([0.2], 51.0),
    ([0.098], 2.0), ([0.03], 2.0)])
def test_short_cycle_keeps_the_parent_slice(walls, seconds):
    delay_s, length_s, before = run.slice_plan(walls, seconds)
    assert (delay_s, length_s) == parent_rule(sum(walls), seconds)
    assert before is False


# Q3 and Q18 at SF10, and the traced CPU rehearsal of the join cell (a q3
# of 0.43 s at SF0.01, whose three cycles no longer fit 0.8 of 2 s)
@pytest.mark.parametrize("walls, seconds", [
    ([13.5], 51.0), ([20.9], 51.0), ([22.8], 51.0), ([6.0, 7.5], 51.0),
    ([50.0], 51.0), ([0.43], 2.0)])
def test_long_cycle_opens_before_the_window(walls, seconds):
    assert run.slice_plan(walls, seconds) == (0.0, run.UNTIL, True)


SCALE = 0.01        # the simulation's clock runs a hundred times fast
SECONDS = 51.0


class Client:
    """A statement client whose every statement takes its wall, drawn
    within 5% of ``wall`` from the seed."""

    def __init__(self, wall, rng):
        self.wall, self.rng = wall, rng
        self.stats_history, self.last_query_id = [], None

    def execute(self, sql, timeout_s):
        time.sleep(self.wall * SCALE * self.rng.uniform(0.95, 1.05))
        return ["x"], [[1]]


@pytest.fixture
def no_profiler(monkeypatch):
    import jax

    monkeypatch.setattr(jax.profiler, "start_trace", lambda *a, **k: None)
    monkeypatch.setattr(jax.profiler, "stop_trace", lambda: None)


@pytest.mark.parametrize("clients", [1, 4])
@pytest.mark.parametrize("wall", [13.5, 20.9, 21.6, 25.6, 35.0, 50.0])
def test_a_long_slice_holds_whole_statements(no_profiler, wall, clients):
    rng = random.Random(SEED + int(wall * 10) + clients)
    cell = {"statements": {"q": "select 1"},
            "traffic": {"statements": ["q"], "clients": clients,
                        "loop": "closed", "order": "cycle"}}
    runner = types.SimpleNamespace(new_client=lambda user: Client(wall, rng))
    delay_s, length_s, until = run.slice_plan([wall], SECONDS)
    assert until
    tracer = run.SliceTracer(None, delay_s, length_s)
    tracer.start()
    _start, samples = run.run_window(runner, cell, SEED, SECONDS * SCALE,
                                     tracer, until)
    assert not tracer.is_alive() and tracer.error is None
    # every statement of the window, so each client's first, and two or
    # more wherever two fit in the window
    assert all(op["start"] >= tracer.start_epoch
               and op["end"] <= tracer.end_epoch for op in samples)
    assert sorted({op["client"] for op in samples}) == list(range(clients))
    if 2 * wall * 1.05 <= SECONDS:
        assert len(samples) >= 2 * clients
