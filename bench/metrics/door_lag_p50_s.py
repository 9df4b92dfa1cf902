"""Door lag, median over every request due in the window: from its
scheduled due time to the engine taking it from the door
(``ServeRequest.released_s``). The first of the three parts of its time to
first token (``stamps.ttft_split``)."""
from stamps import part_median


def read(ctx):
    return part_median(ctx, 0)
