"""Device-call time per PCG loop trip (ms): the sum of the
``solver.solve`` and ``solver.refine`` spans (each holds one device call,
from the copy of its right-hand side to the read-back of its answer) over
the sum of their ``loops``; calls that ran no trip are left out.  The
group's host phases between calls are not in it."""

CALLS = ("solver.solve", "solver.refine")


def read(ctx):
    calls = [e for e in ctx.get("spans") or ()
             if e["name"] in CALLS and "dur_ns" in e
             and e.get("args", {}).get("loops", 0) > 0]
    loops = sum(e["args"]["loops"] for e in calls)
    if not loops:
        return None
    return sum(e["dur_ns"] for e in calls) / 1e6 / loops
