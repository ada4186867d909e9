"""Share of a pass's wall time in which the host is not waiting for the
device: 100 × (1 − Σ ``seq.wait`` ÷ Σ ``seq.score_pass``) over the
window's passes, from the program's own spans."""

from benchmark.seq_spans import host_pct as read  # noqa: F401
