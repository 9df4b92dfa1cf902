"""Peak device memory in use by the end of the run's window, in percent of
the memory the runtime can allocate (``peak_bytes_in_use /
bytes_limit``)."""


def read(ctx):
    m = ctx.memory
    return 100.0 * m["peak"] / m["limit"] if m.get("limit") else None
