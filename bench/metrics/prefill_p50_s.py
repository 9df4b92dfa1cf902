"""Prefill, median over every request due in the window: from its leaving
the waiting list to its first token on the host
(``ServeRequest.first_token_s``). The last of the three parts of its time
to first token (``stamps.ttft_split``)."""
from stamps import part_median


def read(ctx):
    return part_median(ctx, 2)
