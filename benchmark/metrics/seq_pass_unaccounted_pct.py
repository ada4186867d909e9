"""Share of the window's passes that no step's device time accounts
for: 100 × (1 − Σ ``device_ms`` ÷ Σ ``seq.score_pass``), both from the
program's root spans: the inside counterpart of ``device_idle_pct.seq``."""

from benchmark.seq_steps import pass_unaccounted_pct as read  # noqa: F401
