"""Mean queue wait of a served request (ms): the window's change of the
daemon's ``serve.queue_wait_ms`` histogram, its sum over its count."""


def read(ctx):
    total, count = ctx.get("queue_wait") or (0.0, 0)
    return total / count if count else None
