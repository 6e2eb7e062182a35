"""Device idle share while serving (%): ``1 - busy / window`` of the
profiled window."""


def read(ctx):
    t = ctx.get("trace")
    if not t or not t["window_s"] or not t["busy_s"]:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
