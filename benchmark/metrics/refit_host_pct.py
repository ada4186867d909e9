"""Share of a refit cycle's wall time that is not the train steps:
100 × (1 − Σ ``live.retrain.steps`` ÷ Σ ``live.retrain``) over the
window's cycles, from the program's own spans."""

from benchmark.program_spans import host_pct as read  # noqa: F401
