"""How far the scorer's own per-step device times (``seq.wait.step``'s
``device_ms``, from ordered waits on the host's clock) are from the
device trace's: 100 × |Σ ``device_ms`` of the window's passes − Σ runs
of the step programs in the traced window| ÷ the latter. Keeps the
inside number honest."""

from benchmark.seq_steps import device_gap_pct as read  # noqa: F401
