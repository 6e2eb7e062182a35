"""Group time per PCG iteration of its slowest column (ms): the sum of
``solver.group`` spans (they end after the solution is read back) over
the sum of their ``max_iters``, both passes."""


def read(ctx):
    groups = [e for e in ctx.get("spans") or ()
              if e["name"] == "solver.group" and "dur_ns" in e
              and "max_iters" in e.get("args", {})]
    iters = sum(e["args"]["max_iters"] for e in groups)
    if not iters:
        return None
    return sum(e["dur_ns"] for e in groups) / 1e6 / iters
