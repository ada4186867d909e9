"""``core/cache.py``'s count of compiles: the one ``jax.monitoring``
listener on a fresh ``jax.jit``, a traced function inside another's
trace counted once, and ``compile_ms`` on the refit cycle's root."""

import time

import jax
import jax.numpy as jnp
import pytest

from routest_tpu.core import cache
from routest_tpu.obs import get_registry

TRACE, LOWER, BACKEND, LOAD = cache.STAGES


class _Growth:
    """What the two families grew by since the test began."""

    def __init__(self) -> None:
        self._was = {name: self._now(name) for name in (
            "rtpu_compiles_total", "rtpu_compile_seconds_total")}

    @staticmethod
    def _now(name):
        return {k[0]: c.value for k, c in get_registry().get(name).items()}

    def of(self, name):
        return {k: v - self._was[name][k] for k, v in self._now(name).items()}

    def seconds(self, stage="backend"):
        return (cache.compile_seconds(stage)
                - self._was["rtpu_compile_seconds_total"][stage])


@pytest.fixture
def counted():
    """The listener bound to the process registry (whatever an earlier
    test left it bound to)."""
    cache._children = None
    cache.count_compiles()
    return _Growth()


def test_the_stages_are_jaxs_own_events():
    from jax._src import dispatch

    assert cache.STAGES[dispatch.JAXPR_TRACE_EVENT] == "trace"
    assert cache.STAGES[dispatch.JAXPR_TO_MLIR_MODULE_EVENT] == "lower"
    assert cache.STAGES[dispatch.BACKEND_COMPILE_EVENT] == "backend"
    assert cache.STAGES[LOAD] == "cache_load"


def test_a_fresh_jit_raises_trace_lower_and_backend(counted):
    assert counted.of("rtpu_compiles_total") == dict.fromkeys(
        cache.STAGES.values(), 0.0)

    def fresh(x):
        return jnp.tanh(x @ x).sum()

    f = jax.jit(fresh)
    f(jnp.ones((64, 64))).block_until_ready()
    n = counted.of("rtpu_compiles_total")
    s = counted.of("rtpu_compile_seconds_total")
    for stage in ("trace", "lower", "backend"):
        assert n[stage] >= 1 and s[stage] > 0.0, stage
    assert counted.seconds() == s["backend"]
    assert counted.seconds("trace") == s["trace"]
    # the second call is a hit in the function's own cache
    f(jnp.ones((64, 64))).block_until_ready()
    assert counted.of("rtpu_compiles_total") == n


def test_the_listener_is_registered_once_a_process(counted):
    from jax._src import monitoring

    cache.count_compiles()
    cache._children = None
    cache.count_compiles()
    assert monitoring._event_duration_secs_listeners.count(
        cache._on_duration) == 1


def test_a_trace_inside_a_trace_is_counted_once_in_seconds(counted,
                                                           monkeypatch):
    """JAX reports the inner function's trace as an event of its own,
    inside the outer one's time; events arrive as they END."""
    now = [1000.0]
    monkeypatch.setattr(cache, "time",
                        type("T", (), {"time": staticmethod(lambda: now[0])}))
    cache._traces.open = []
    now[0] = 1000.30
    cache._on_duration(TRACE, 0.10)         # inner a: 1000.20-1000.30
    now[0] = 1000.50
    cache._on_duration(TRACE, 0.05)         # inner b: 1000.45-1000.50
    now[0] = 1000.60
    cache._on_duration(TRACE, 0.50)         # outer: 1000.10-1000.60
    now[0] = 1001.00
    cache._on_duration(TRACE, 0.20)         # the next, on its own
    assert counted.seconds("trace") == pytest.approx(0.70)
    assert counted.of("rtpu_compiles_total")["trace"] == 4
    # the other stages are summed as they come
    cache._on_duration(LOWER, 0.25)
    cache._on_duration(BACKEND, 2.0)
    cache._on_duration(LOAD, 0.5)
    cache._on_duration(BACKEND, 1.0, fun_name="again")
    cache._on_duration("/jax/some/other/event", 9.0)
    assert counted.of("rtpu_compile_seconds_total") == pytest.approx(
        {"trace": 0.70, "lower": 0.25, "backend": 3.0, "cache_load": 0.5})


def test_nothing_counts_before_the_switch(monkeypatch):
    monkeypatch.setattr(cache, "_children", None)
    cache._on_duration(BACKEND, 1.0)
    assert cache.compile_seconds() == 0.0


def test_the_cycle_that_compiled_says_so_on_its_root(tmp_path, tracer,
                                                     counted):
    from test_live_trainer_spans import _trainer

    tr = _trainer(tmp_path)
    assert tr.run_once()["trained"] is True
    assert tr.run_once()["trained"] is True
    first, second = [s for s in tracer.buffer.snapshot()
                     if s["name"] == "live.retrain"]
    assert 0.0 < first["attrs"]["compile_ms"] <= first["duration_ms"]
    assert "compile_ms" not in second["attrs"]
    for root in (first, second):
        assert root["attrs"]["cpu_ms"] > 0.0 and root["attrs"]["gc_ms"] >= 0.0
        assert root["attrs"]["result"] == "saved"


def test_an_unrecorded_cycle_reads_nothing_of_the_host(tmp_path, monkeypatch):
    from routest_tpu.obs import Tracer, configure_tracer, get_tracer, host
    from test_live_trainer_spans import _trainer

    monkeypatch.setattr(host, "_now", lambda: pytest.fail("read"))
    old = get_tracer()
    configure_tracer(Tracer(enabled=False))
    try:
        assert _trainer(tmp_path).run_once()["trained"] is True
    finally:
        configure_tracer(old)
