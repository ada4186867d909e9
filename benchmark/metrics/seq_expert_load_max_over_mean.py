"""Fullest held expert over the mean one, averaged over the last pass's
steps and expert layers, from the scorer's gauge
``rtpu_seq_expert_load_max_over_mean``: how uneven the grouped product's
groups are."""

from benchmark.seq_spans import expert_load_max_over_mean as read  # noqa: F401
