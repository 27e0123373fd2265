#!/usr/bin/env python3
"""One run of one benchmark cell on the chip.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process: a coordinator and the cell's workers (``DistributedQueryRunner``)
on this machine's TPU, the cell's statements warmed until an execution builds
no new XLA program (set-up, ``setup_s``), then closed-loop clients for
``--seconds``.  Every answer is compared with a plain numpy reference.  The
last line of standard output is the result; earlier lines are one JSON object
each, for the reader of a log.  Without a TPU, or with fewer chips than the
cell asks for, it exits non-zero and prints no result.  See README.md.
"""

from __future__ import annotations

import time

PROCESS_START = time.time()   # set-up is counted from here

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import threading  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import (check, load, manifest, metrics, observe,  # noqa: E402
                       refdata, trace_reduce)

MAX_WARMUPS = 4          # executions of one statement before giving up
DETAIL_THREADS = 8       # fetching query details after the window


def emit(obj: dict) -> None:
    print(json.dumps(obj), flush=True)


class References(threading.Thread):
    """The expected rows of the cell's statements, computed beside the
    warm-up (they are needed only when answers are judged)."""

    def __init__(self, cell: dict, scale: float):
        super().__init__(daemon=True)
        self.cell, self.scale = cell, scale
        self.want: dict = {}
        self.statement_bytes: dict = {}
        self.rows: dict = {}
        self.seconds = 0.0
        self.error: BaseException | None = None

    def run(self) -> None:
        try:
            t0 = time.perf_counter()
            wanted: dict = {}
            for ref in self.cell["references"].values():
                for table, cols in ref.COLUMNS.items():
                    wanted.setdefault(table, set()).update(cols)
            cols, nbytes = refdata.host_columns(
                self.cell["config"]["connector"], self.scale, wanted)
            for name, ref in self.cell["references"].items():
                self.want[name] = ref.reference(cols)
                self.statement_bytes[name] = refdata.must_read_bytes(
                    ref.COLUMNS, nbytes)
            self.rows = {t: int(len(cols[sorted(c)[0]]))
                         for t, c in wanted.items()}
            self.seconds = time.perf_counter() - t0
        except BaseException as e:  # re-raised by the main thread
            self.error = e


UNTIL = "until"    # a slice that ends when the window says so


def slice_plan(warm_walls, seconds: float) -> tuple:
    """(delay_s, length_s or ``UNTIL``, opens_before_window) of the traced
    slice, from the statements' warm walls alone.

    Short cycle (three cycles and half a second fit in 0.8 of the window):
    the profiler starts ``delay_s`` after the window opens and runs
    ``length_s``, enough for three cycles, so every statement lies whole in
    it at least once.  Long cycle: such a slice could hold no whole
    statement, so the profiler starts before the window's first statement
    is sent and runs until the window's last statement has ended
    (``run_window``)."""
    cycle = sum(warm_walls)
    if 3.0 * cycle + 0.5 <= 0.8 * seconds:
        return (min(1.0, 0.1 * seconds),
                min(max(3.0, 3.0 * cycle + 0.5), 0.8 * seconds), False)
    return 0.0, UNTIL, True


class SliceTracer(threading.Thread):
    """Profiles a steady slice of the window and marks the host's clock in
    the trace, so that device events can be set beside the coordinator's
    spans.  ``started`` is set once the profiler runs (or has failed to
    start); a slice of length ``UNTIL`` ends when ``stop`` is set."""

    def __init__(self, trace_dir: str, delay_s: float, length_s):
        super().__init__(daemon=True)
        self.dir, self.delay_s, self.length_s = trace_dir, delay_s, length_s
        self.started, self.stop = threading.Event(), threading.Event()
        self.start_epoch = self.end_epoch = self.stop_s = None
        self.error: BaseException | None = None

    def run(self) -> None:
        import jax

        def mark() -> None:
            with jax.profiler.TraceAnnotation(
                    f"{trace_reduce.CLOCK_MARK}{time.time_ns()}"):
                pass

        try:
            time.sleep(self.delay_s)
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0   # host threads: TraceMe only
            options.host_tracer_level = 1
            jax.profiler.start_trace(self.dir, profiler_options=options)
            try:
                self.start_epoch = time.time()
                self.started.set()
                if self.length_s == UNTIL:
                    mark()
                    while not self.stop.wait(0.5):
                        mark()
                else:
                    stop_at = time.perf_counter() + self.length_s
                    while time.perf_counter() < stop_at:
                        mark()
                        time.sleep(min(0.5, max(stop_at - time.perf_counter(),
                                                0.0)))
                mark()
                self.end_epoch = time.time()
            finally:
                t0 = time.perf_counter()
                jax.profiler.stop_trace()
                self.stop_s = time.perf_counter() - t0
        except BaseException as e:
            self.error = e
        finally:
            self.started.set()   # a window that waits for it opens anyway


def run_window(runner, cell: dict, seed: int, seconds: float,
               tracer: SliceTracer | None, until: bool) -> tuple:
    """``load.closed_loop`` beside the tracer.  A slice of length ``UNTIL``
    starts before the window's first statement is sent and stops once the
    last has ended, so every statement of the window lies whole in it."""
    if until:
        tracer.started.wait()
    try:
        return load.closed_loop(runner.new_client, cell["statements"],
                                cell["traffic"], seed, seconds)
    finally:
        if tracer is not None:
            tracer.stop.set()
            tracer.join()


def warm_up(cell: dict, runner, xla: observe.XlaCompiles) -> dict:
    """Executes each statement until one execution builds no XLA program
    (compiled, or loaded from the persistent cache: JAX raises the same
    event for both).  Returns {statement: {first_exec_s, warm_wall_s,
    executions, ops}}."""
    client = load.bench_client(runner.new_client, "bench-warmup")
    out = {}
    for name, sql in cell["statements"].items():
        ops, built = [], None
        for _ in range(MAX_WARMUPS):
            mark = len(xla.events)
            op = load.execute(client, name, sql, -1)
            ops.append(op)
            built = len(xla.since(mark))
            if op["error"]:
                raise RuntimeError(f"warm-up of {name}: {op['error']}")
            if not built:
                break
        out[name] = {"first_exec_s": ops[0]["wall_s"],
                     "warm_wall_s": ops[-1]["wall_s"],
                     "executions": len(ops), "still_building": built,
                     "ops": ops}
        emit({"phase": "warmup", "statement": name,
              "executions": len(ops), "first_exec_s": ops[0]["wall_s"],
              "warm_wall_s": ops[-1]["wall_s"],
              "xla_builds_in_last_execution": built})
    return out


def fetch_accounts(uri: str, ops: list, with_spans: bool) -> tuple:
    """({query id: detail}, {query id: span tree}) from the coordinator,
    after the window, outside every timing."""
    from concurrent.futures import ThreadPoolExecutor

    ids = [op["query_id"] for op in ops if op["query_id"]]

    def one(qid: str):
        try:
            return (qid, observe.query_detail(uri, qid),
                    observe.query_spans(uri, qid) if with_spans else None)
        except Exception:   # judged as "no query detail"
            return qid, None, None

    with ThreadPoolExecutor(DETAIL_THREADS) as pool:
        got = list(pool.map(one, ids))
    return ({q: d for q, d, _ in got if d is not None},
            {q: s for q, _, s in got if s is not None})


def judge_all(ops: list, want: dict, details: dict, config: dict,
              counted_fallbacks: dict) -> list:
    """Marks every operation ``ok`` or not; returns the failures."""
    failed = []
    for op in ops:
        op["why_failed"] = check.judge(
            op, want[op["statement"]], details.get(op["query_id"]), config,
            counted_fallbacks)
        op["ok"] = op["why_failed"] is None
        if not op["ok"]:
            failed.append(op)
    return failed


def reduce_trace(tracer: SliceTracer, samples: list, spans: dict,
                 keep_trace: str | None) -> dict | None:
    if tracer.error is not None:
        emit({"phase": "trace", "error": repr(tracer.error)})
        return None
    file = trace_reduce.newest_xplane(tracer.dir)
    if file is None or tracer.start_epoch is None:
        emit({"phase": "trace", "error": "the profiler wrote no trace"})
        return None
    if keep_trace:
        os.makedirs(keep_trace, exist_ok=True)
        shutil.copy(file, keep_trace)
    t0 = time.perf_counter()
    trace = trace_reduce.load(file)
    trees: dict = {}
    for op in samples:
        if op["query_id"] in spans:
            trees.setdefault(op["statement"], []).append(
                spans[op["query_id"]])
    flat = trace_reduce.flatten_spans(trees)
    reduced = trace_reduce.reduce(trace, tracer.start_epoch,
                                  tracer.end_epoch, flat)
    lo, hi = tracer.start_epoch, tracer.end_epoch
    whole: dict = {}
    for op in samples:
        if op["start"] >= lo and op["end"] <= hi:
            whole[op["statement"]] = whole.get(op["statement"], 0) + 1
    emit({"phase": "trace", "file_bytes": os.path.getsize(file),
          "stop_s": tracer.stop_s, "reduce_s": time.perf_counter() - t0,
          "planes": [p["name"] for p in trace["planes"]],
          "slice": {"delay_s": tracer.delay_s, "length_s": tracer.length_s,
                    "seconds": hi - lo},
          "whole_samples": whole, "reduced": reduced is not None})
    if keep_trace:
        with open(os.path.join(keep_trace, "reduce_input.json"), "w") as f:
            json.dump({"start": tracer.start_epoch, "end": tracer.end_epoch,
                       "spans": flat, "outline": trace_reduce.outline(trace),
                       "trace": trace}, f)
    return reduced


def run_cell(cell: dict, seed: int, seconds: float, trace: bool, devices,
             peaks: dict, scale: float | None = None,
             keep_trace: str | None = None,
             process_start: float | None = None) -> dict:
    """Set-up, window, checks, metrics.  ``peaks`` is the device's entry
    of peaks.json; ``scale`` overrides the configuration's only for the
    CPU rehearsal in ``tests/``.  Returns the result line as a dict."""
    import jax

    from presto_tpu.config import DEFAULT
    from presto_tpu.server.dqr import DistributedQueryRunner

    process_start = process_start or time.time()
    config = cell["config"]
    scale = config["scale"] if scale is None else scale
    xla = observe.XlaCompiles()
    cache_dir = jax.config.jax_compilation_cache_dir
    emit({"phase": "start", "cell": cell["name"], "seed": seed,
          "device_kind": devices[0].device_kind, "devices": len(devices),
          "scale": scale, "cache_dir": cache_dir,
          "cache_entries_at_start": observe.cache_entries(cache_dir)})

    refs = References(cell, scale)
    refs.start()
    engine = dataclasses.replace(DEFAULT, **config["engine_config"])
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    try:
        with DistributedQueryRunner.tpch(scale=scale,
                                         n_workers=config["workers"],
                                         config=engine) as runner:
            uri = runner.coordinator.uri
            warm = warm_up(cell, runner, xla)
            refs.join()
            if refs.error is not None:
                raise refs.error
            emit({"phase": "reference", "seconds": refs.seconds,
                  "rows": refs.rows,
                  "statement_must_read_bytes": refs.statement_bytes})
            setup_events, setup_hits = xla.since(0), xla.cache_hits
            tracer, until = None, False
            if trace:
                delay_s, length_s, until = slice_plan(
                    [w["warm_wall_s"] for w in warm.values()], seconds)
                tracer = SliceTracer(trace_dir, delay_s, length_s)
                tracer.start()
            setup_s = time.time() - process_start
            window_start, samples = run_window(runner, cell, seed, seconds,
                                               tracer, until)
            window_events = xla.since(len(setup_events))
            warm_ops = [op for w in warm.values() for op in w["ops"]]
            details, spans = fetch_accounts(uri, warm_ops + samples, trace)
            fallbacks = dict(
                runner.coordinator.device_exchange_counters["fallbacks"])
        reduced = (reduce_trace(tracer, samples, spans, keep_trace)
                   if trace else None)
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)

    failed = judge_all(warm_ops + samples, refs.want, details, config,
                       fallbacks)
    for op in failed[:5]:
        emit({"phase": "failed_operation", "statement": op["statement"],
              "query_id": op["query_id"], "why": op["why_failed"]})
    good = [op for op in samples if op["ok"]]
    max_rel_err = max((op.get("max_rel_err", 0.0)
                       for op in warm_ops + samples), default=0.0)
    by_statement = {name: [op for op in good if op["statement"] == name]
                    for name in cell["statements"]}
    walls = {n: [op["wall_s"] for op in ops]
             for n, ops in by_statement.items()}
    emit({"phase": "window", "seconds": seconds,
          "samples": {n: len(w) for n, w in walls.items()},
          "median_wall_s": {n: statistics.median(w) if w else None
                            for n, w in walls.items()},
          "wall_s": {n: metrics.distribution(w) for n, w in walls.items()},
          "responses_per_query": {
              n: metrics.distribution([op["responses"] for op in ops])
              for n, ops in by_statement.items()},
          "all_statements_samples": len(good),
          "xla_builds_in_setup": len(setup_events),
          "xla_build_seconds_in_setup": sum(e[2] for e in setup_events),
          "persistent_cache_hits_in_setup": setup_hits,
          "xla_builds_in_window": len(window_events),
          "window_compiled": sorted({e[1] for e in window_events}),
          "max_rel_err": max_rel_err})

    peak = observe.memory_peak_bytes(devices)
    run = {
        "cell": cell, "seed": seed, "seconds": seconds,
        "samples": good, "window_start": window_start,
        "setup": {"seconds": setup_s, "warm": warm,
                  "compile_events": setup_events,
                  "reference_s": refs.seconds},
        "window_compile_events": window_events,
        "details": details, "spans": spans, "trace": reduced,
        "statement_bytes": refs.statement_bytes,
        "memory_peak_bytes": peak,
        "peaks": peaks, "chips": len(devices),
    }
    group, entries = (("layer_metrics", cell["per_layer"]) if trace
                      else ("end_to_end", cell["end_to_end"]))
    values = {}
    for entry in entries:
        value = manifest.load_module(group, entry["name"]).read(run)
        if value is not None:
            values[entry["name"]] = {"value": value, "unit": entry["unit"]}
    emit({"phase": "end",
          "cache_entries_at_end": observe.cache_entries(cache_dir),
          "memory_peak_bytes": peak,
          "warm_failed": sum(not op["ok"] for op in warm_ops)})

    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": peak}
    result = {"correct": not failed and bool(samples),
              "attempted": len(samples),
              "failed": sum(not op["ok"] for op in samples),
              "metrics": values, "device": device}
    if trace and reduced is not None:
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        result["breakdown"] = {"device_ops": reduced["device_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
    # what ``correct`` compared, each number beside its limit (the key comes
    # last in the line; main() repeats it on standard error)
    result["compared"] = {
        "failed_operations": {"value": len(failed), "limit": 0,
                              "why": [op["why_failed"] for op in failed[:3]]},
        "max_rel_err": {"value": max_rel_err,
                        "limit": config["guarantees"]["double_rtol"]}}
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", default=None, metavar="DIR",
                    help="copy the raw .xplane.pb here, and what was "
                         "handed to trace_reduce.reduce (for reading a "
                         "trace by hand, and for the tests' fixture)")
    args = ap.parse_args(argv)
    cell = manifest.cell(args.workload)
    # the engine's spill tier defaults to a fixed path under /tmp
    os.environ.setdefault("PRESTO_TPU_SPILL", os.path.join(
        tempfile.gettempdir(), "presto_tpu_spill"))

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"benchmark: needs a TPU, JAX found "
              f"{devices[0].platform!r}", file=sys.stderr)
        return 1
    if len(devices) < cell["chips"] or (cell["chips"] > 1
                                        and len(devices) != cell["chips"]):
        print(f"benchmark: {cell['name']} needs {cell['chips']} chip(s), "
              f"JAX found {len(devices)}", file=sys.stderr)
        return 1

    peaks = manifest.load_json("peaks.json").get(devices[0].device_kind)
    if peaks is None:
        print(f"benchmark: peaks.json has no {devices[0].device_kind!r}",
              file=sys.stderr)
        return 1

    import presto_tpu  # noqa: F401 - places the compile cache (config.py)
    from presto_tpu import native

    if native.lib() is None:
        print("benchmark: presto_tpu.native did not build or load; the "
              "exchange wire would run without LZ4", file=sys.stderr)
        return 1
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                      devices[:cell["chips"]], peaks,
                      keep_trace=args.keep_trace,
                      process_start=PROCESS_START)
    emit(result)
    for name, c in result["compared"].items():
        print(f"compared {name}: {c['value']} (limit {c['limit']})"
              + "".join(f"\n  {why}" for why in c.get("why", ())),
              file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
