"""One module per kind of traffic. A driver exposes ``ANNOTATIONS`` (the
harness's host spans, innermost first, the last being the whole window)
and ``Driver(run)`` with ``window(seconds)``, ``end_to_end()``,
``counts()``, ``release()`` and ``check()``; ``run`` is a
``benchmark.run.Run``."""
