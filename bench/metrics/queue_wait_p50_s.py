"""Queue wait, median over every request due in the window: from the
engine taking it from the door to its leaving the waiting list into a
prefill (``ServeRequest.admitted_s``). The second of the three parts of its
time to first token (``stamps.ttft_split``)."""
from stamps import part_median


def read(ctx):
    return part_median(ctx, 1)
