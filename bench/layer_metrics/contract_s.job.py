"""Hierarchy contraction per job, all levels (s): the sum of ``hierarchy.contract`` spans over the jobs of the window."""


def read(ctx):
    spans = [e["dur_ns"] for e in ctx.get("spans") or ()
             if e["name"] == "hierarchy.contract" and "dur_ns" in e]
    jobs = ctx.get("jobs")
    return sum(spans) / 1e9 / jobs if jobs and spans else None
