"""Mean megabytes (1e6 bytes) a cycle of the window handed to the
device: the ``bytes`` attribute of ``live.retrain.upload``, which the
program sets to what the cycle really sent, over the same roots that
``benchmark/program_spans.py`` selects (the last ``counts["cycles"]``
saved ones). ``None`` where a root, the child or the attribute is
missing."""

from benchmark.program_spans import ROOT


def read(ctx):
    n = int(ctx["counts"].get("cycles", 0))
    if n <= 0:
        return None
    from routest_tpu.obs import get_tracer

    spans = get_tracer().buffer.snapshot()
    roots = [s["span_id"] for s in spans if s["name"] == ROOT
             and s["attrs"].get("result") == "saved"][-n:]
    if len(roots) < n:
        return None
    sent = {s["parent_id"]: s["attrs"].get("bytes") for s in spans
            if s["name"] == ROOT + ".upload"}
    if any(sent.get(r) is None for r in roots):
        return None
    return sum(sent[r] for r in roots) / len(roots) / 1e6
