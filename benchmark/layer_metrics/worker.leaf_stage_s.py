"""The longest stage span among the query's leaf stages (those that fetch
nothing from an exchange: table scans and what is fused behind them), median
per window query."""

import statistics

LAYER = "worker task, drivers"
UNIT = "s"
SOURCE = "program_span"
MOVES = "query_geomean_s"


def read(run: dict):
    values = []
    for op in run["samples"]:
        detail = run["details"].get(op["query_id"])
        tree = run["spans"].get(op["query_id"])
        if detail is None or tree is None:
            continue
        leaves = {f"stage-{fid}" for fid, st in
                  (detail.get("stageStats") or {}).items()
                  if not st.get("exchange_fetched")}
        spans = [c["durationS"] for c in tree.get("children", [])
                 if c["kind"] == "stage" and c["name"] in leaves]
        if spans:
            values.append(max(spans))
    return statistics.median(values) if values else None
