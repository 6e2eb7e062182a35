"""Mean PCG iterations per column of a served request, both passes, from the
responses."""


def read(ctx):
    iters = ctx.get("iters") or []
    return sum(iters) / len(iters) if iters else None
