"""Seconds the first warm-up execution of each statement spent tracing and
lowering programs before XLA compiled or loaded them
(``queryStats.xla_trace_lower_ns``, the program's own account of JAX's
``jaxpr_trace_duration`` and ``jaxpr_to_mlir_module_duration`` events),
summed over the cell's statements."""

LAYER = "XLA compile + cache"
UNIT = "s"
SOURCE = "program_counter"
MOVES = "setup_s"


def read(run: dict):
    total = 0
    for warm in run["setup"]["warm"].values():
        detail = run["details"].get(warm["ops"][0]["query_id"]) or {}
        ns = (detail.get("queryStats") or {}).get("xla_trace_lower_ns")
        if ns is None:      # a program without the account
            return None
        total += ns
    return total / 1e9
