"""Level-0 V-cycle share of the HBM roofline (%): the byte model's bytes
of the level-0 V-cycle over the device time of the ops under
``vcycle.L0.`` scopes, over peak HBM bytes/s, summed over the recorded
device solve calls wholly inside the profiled window."""
from benchkit import devtrace
from benchkit.roofline import vcycle_level_bytes


def read(ctx):
    trace, calls, levels = (ctx.get("trace_plain"), ctx.get("calls"),
                            ctx.get("levels"))
    if not trace or not calls or not levels:
        return None
    n0, blocks0 = levels[0]
    moved, dev_ns = 0, 0.0
    for name, start, dur in devtrace.host_spans(trace, "bench.solve_call."):
        call = calls[int(name.rsplit(".", 1)[1])]
        t = devtrace.scoped_time_ns(trace, "vcycle.L0.", start, start + dur)
        if t <= 0:
            continue
        moved += (call["loops"] + 1) * vcycle_level_bytes(n0, blocks0,
                                                          call["k"])
        dev_ns += t
    if dev_ns <= 0:
        return None
    return 100.0 * moved / (dev_ns / 1e9) / float(
        ctx["peaks"]["hbm_bytes_per_s"])
