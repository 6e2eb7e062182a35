"""Host time of a solve group (ms): the mean over ``solver.group`` spans
of the group's duration less the device-call spans (``solver.solve``,
``solver.refine``, with their ``loops``) that lie inside it on its
thread.  What each group adds to every request in it besides the device
calls: stacking, f64 residuals, resolution."""

CALLS = ("solver.solve", "solver.refine")


def read(ctx):
    spans = [e for e in ctx.get("spans") or () if "dur_ns" in e]
    calls = [e for e in spans if e["name"] in CALLS
             and "loops" in e.get("args", {})]
    host = []
    for g in spans:
        if g["name"] != "solver.group":
            continue
        g0, g1 = g["ts_ns"], g["ts_ns"] + g["dur_ns"]
        inside = [c["dur_ns"] for c in calls if c["tid"] == g["tid"]
                  and g0 <= c["ts_ns"] and c["ts_ns"] + c["dur_ns"] <= g1]
        if inside:
            host.append(g["dur_ns"] - sum(inside))
    if not host:
        return None
    return sum(host) / len(host) / 1e6
