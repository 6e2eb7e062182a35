"""Mean PCG iterations per column of a job, both passes, from the
responses."""


def read(ctx):
    iters = ctx.get("iters") or []
    return sum(iters) / len(iters) if iters else None
