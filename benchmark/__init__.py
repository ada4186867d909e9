"""routest-tpu's benchmark: the yardstick later PRs are measured with.

Everything here is the benchmark's own: traffic generation, the plain
references, the FLOP / byte counts, the table of peaks, the reduction
from a profiler trace to metrics and the comparison that decides
``correct``. From the program (``routest_tpu``) it takes only the system
under test. ``run.py`` is the one entry; it finds a cell's configuration,
traffic mix, driver and per-layer readers by the names in
``BENCHMARK.json``, so a later PR adds cells, mixes, drivers and readers
as files and manifest entries, never by editing a file that is here.
"""
