"""Keys the full layers and the prediction module's multiplied (whole
chunks up to a block's last key, padded tokens included) over the keys
their real queries saw (``t + 1``): ``benchmark/gqa_keys.py``."""

from benchmark.gqa_keys import visited_over_needed


def read(ctx):
    return visited_over_needed("full")
