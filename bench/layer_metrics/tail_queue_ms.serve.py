"""Queueing in the latency tail (ms).  Each request's latency is its
queue wait (``waits_ms`` of its ``serve.flush_cycle``) plus the time from
its cycle's start to the end of the ``solver.group`` that served it,
joined by ticket id; the metric is the mean queue wait of the requests at
or above the nearest-rank 95th percentile of that latency."""
from benchkit.stats import percentile


def read(ctx):
    spans = ctx.get("spans") or ()
    group_end = {}
    for e in spans:
        if e["name"] == "solver.group" and "tickets" in e.get("args", {}):
            for t in e["args"]["tickets"]:
                group_end[t] = e["ts_ns"] + e["dur_ns"]
    pairs = []      # (latency ms, queue wait ms)
    for e in spans:
        args = e.get("args", {})
        if e["name"] != "serve.flush_cycle" or "tickets" not in args:
            continue
        for t, wait in zip(args["tickets"], args["waits_ms"]):
            if t in group_end:
                pairs.append((wait + (group_end[t] - e["ts_ns"]) / 1e6, wait))
    if not pairs:
        return None
    tail = percentile([lat for lat, _ in pairs], 95)
    waits = [w for lat, w in pairs if lat >= tail]
    return sum(waits) / len(waits)
