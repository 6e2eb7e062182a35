"""Padding columns in served device solves (%): ``1 - sum k / sum
k_pad`` over the window's ``solver.group`` spans."""


def read(ctx):
    groups = [e["args"] for e in ctx.get("spans") or ()
              if e["name"] == "solver.group" and "dur_ns" in e
              and "k_pad" in e.get("args", {})]
    k_pad = sum(a["k_pad"] for a in groups)
    if not k_pad:
        return None
    return 100.0 * (1.0 - sum(a["k"] for a in groups) / k_pad)
