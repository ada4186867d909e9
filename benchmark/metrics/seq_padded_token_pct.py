"""Padded tokens as a share of the tokens the scorer computed, from its
counter ``rtpu_seq_tokens_total{kind}``: what the length ladder costs."""

from benchmark.seq_spans import padded_token_pct as read  # noqa: F401
