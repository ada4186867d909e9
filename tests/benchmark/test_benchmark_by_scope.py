"""``benchmark/tools/by_scope.py``: which scope an operation's path is
charged to, and the recorded od-score xplane (no ``lm.…`` scope in it)
summed as one unscoped total."""

import os

import pytest

from _toy import R  # noqa: F401  (puts the repo on the path)

from benchmark.tools import by_scope

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


@pytest.mark.parametrize("path,want", [
    ("jit(_run_step)/lm.L2.swa/while/body/closed_call/"
     "windowed_attention_step/pallas_call",
     ("lm.L2.swa", "while/body/closed_call/windowed_attention_step/"
      "pallas_call")),
    # the innermost scope wins: the radix search inside a full layer
    ("jit(_run_step)/lm.L0.mla/while/body/lm.L0.topk/reduce_sum",
     ("lm.L0.topk", "reduce_sum")),
    ("jit(_run_step)/lm.L3.moe.experts/while/body/dot_general",
     ("lm.L3.moe.experts", "while/body/dot_general")),
    ("jit(_run_step)/lm.head/dot_general", ("lm.head", "dot_general")),
    ("jit(_run_step)/scatter", ("unscoped", "jit(_run_step)/scatter")),
    # a component that only starts like a scope is none
    ("jit(f)/lm.L1.mla:bad/x", ("unscoped", "jit(f)/lm.L1.mla:bad/x")),
    ("", ("unscoped", ""))])
def test_an_operation_is_charged_to_its_innermost_scope(path, want):
    assert by_scope.scope_of(path) == want


def test_the_recorded_od_trace_is_one_unscoped_total():
    pytest.importorskip("tensorflow.tsl.profiler.protobuf.xplane_pb2")
    from benchmark.tools import xplane_spans as xs

    space = xs.read_space(os.path.join(DATA, "trace_od_v5e.xplane.pb"))
    got = by_scope.by_scope(space, "jit", detail="unscoped", top=3)
    assert got["runs"] > 0 and set(got["seconds"]) == {"unscoped"}
    assert got["leaf_seconds"] == pytest.approx(got["seconds"]["unscoped"])
    listed = got["detail"]["unscoped"]
    assert len(listed) == 3
    assert listed[0][2] >= listed[1][2] >= listed[2][2] > 0
    assert sum(op[2] for op in listed) <= got["leaf_seconds"]
    # nothing asked for, nothing listed
    assert by_scope.by_scope(space, "jit")["detail"] == {}
