"""Program spans on the device trace's clock: a recorded span holds a
``jax.profiler.TraceAnnotation`` open where ``jax`` is already imported,
and never imports it."""

import glob
import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import pytest

from routest_tpu.obs import Tracer, configure_tracer, trace_span

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _host_events(directory):
    """name → [(thread line, start_ns, duration_ns)] of ``/host:CPU``."""
    from jax.profiler import ProfileData

    found = glob.glob(os.path.join(directory, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    assert len(found) == 1
    out = {}
    for plane in ProfileData.from_file(found[0]).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for e in line.events:
                out.setdefault(e.name, []).append(
                    (line.name, e.start_ns, e.duration_ns))
    return out


def test_a_span_under_a_capture_lands_on_the_host_plane(tmp_path, tracer):
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation("harness.window"):
            with trace_span("x.y", rows=3):
                with trace_span("x.y.inner"):
                    jnp.arange(8).sum().block_until_ready()
    finally:
        jax.profiler.stop_trace()
    events = _host_events(str(tmp_path))
    assert len(events["x.y"]) == 1 and len(events["x.y.inner"]) == 1
    (line_w, w0, wd), = events["harness.window"]
    (line_o, o0, od), = events["x.y"]
    (line_i, i0, i_d), = events["x.y.inner"]
    # same thread's line, same clock: nested like the spans themselves
    assert line_w == line_o == line_i
    assert w0 <= o0 <= i0 and i0 + i_d <= o0 + od <= w0 + wd
    recorded = {s["name"]: s for s in tracer.buffer.snapshot()}
    assert od / 1e6 == pytest.approx(recorded["x.y"]["duration_ms"],
                                     abs=5.0)


@pytest.mark.parametrize("tracer_kw", [{"enabled": False},
                                       {"sample_rate": 0.0}],
                         ids=["off", "unsampled"])
def test_a_span_that_is_not_recorded_writes_no_annotation(
        tmp_path, tracer, tracer_kw):
    configure_tracer(Tracer(**tracer_kw))           # the fixture restores
    jax.profiler.start_trace(str(tmp_path))
    try:
        with trace_span("x.quiet"):
            pass
    finally:
        jax.profiler.stop_trace()
    assert "x.quiet" not in _host_events(str(tmp_path))


# The package's own ``__init__`` imports JAX (the mesh runtime), so a
# process that is to stay off it (the fleet gateway's tier, chip_smoke's
# parent) reaches ``routest_tpu.obs``, which is stdlib-only, without
# running it: here through a bare package object with the same path.
_NO_JAX = textwrap.dedent("""
    import json, sys, types
    pkg = types.ModuleType("routest_tpu")
    pkg.__path__ = [sys.argv[1]]
    sys.modules["routest_tpu"] = pkg
    from routest_tpu.obs import Tracer, configure_tracer, trace_span
    tracer = configure_tracer(Tracer(enabled=True, sample_rate=1.0))
    with trace_span("a"):
        with trace_span("a.b"):
            pass
    print(json.dumps({
        "spans": [s["name"] for s in tracer.buffer.snapshot()],
        "jax": sorted(m for m in sys.modules
                      if m == "jax" or m.startswith(("jax.", "jaxlib")))}))
""")


def test_a_process_without_jax_records_spans_and_stays_without_it():
    proc = subprocess.run(
        [sys.executable, "-c", _NO_JAX, os.path.join(REPO, "routest_tpu")],
        capture_output=True, text=True, timeout=60, cwd=REPO)
    assert proc.returncode == 0, proc.stderr
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out == {"spans": ["a.b", "a"], "jax": []}
