"""Keys the window layers multiplied (two blocks of 128 a query, padded
tokens included) over the keys their real queries saw (``min(t + 1,
128)``): ``benchmark/gqa_keys.py``."""

from benchmark.gqa_keys import visited_over_needed


def read(ctx):
    return visited_over_needed("window")
