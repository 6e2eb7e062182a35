"""Solve and float64 refinement per job (s): ``solver.group`` minus its
``solver.artifacts`` (the build), over the jobs of the window."""


def read(ctx):
    def total_s(name):
        return sum(e["dur_ns"] for e in ctx.get("spans") or ()
                   if e["name"] == name and "dur_ns" in e) / 1e9

    jobs = ctx.get("jobs")
    groups = total_s("solver.group")
    if not jobs or not groups:
        return None
    return (groups - total_s("solver.artifacts")) / jobs
