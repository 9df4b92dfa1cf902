"""Process start to the first scheduled arrival: loading, weights,
warm-up and every compile or cache load before the window."""


def read(ctx):
    return ctx.setup_s
