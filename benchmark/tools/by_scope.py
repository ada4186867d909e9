"""Device seconds of one traced window by named scope, read from the
xplane itself (``benchmark.trace.Trace`` does not keep the scopes:
PERF.md §7):

    python3 benchmark/tools/by_scope.py trace.xplane.pb \\
        --module jit__run_step --detail 'lm\\.L2\\.swa' --top 12

Every leaf operation of the programs named ``--module…`` (``while``
wrappers left out: their bodies' operations are events of their own) is
charged to the innermost ``lm.…`` component of its ``tf_op`` path, or to
``unscoped``. For the scopes that ``--detail`` matches, the operations
with the most time are listed with what follows the scope in their path
(``while/body/…`` is the loop over blocks of queries). Prints one JSON
object. The trace has to come from a run that compiled its programs (an
empty ``JAX_COMPILATION_CACHE_DIR``): a program loaded from the
persistent cache carries no scopes (PERF.md §6, PR 25).
``benchmark/tools/trace_dump.py`` keeps a traced window's ``.xplane.pb``.
"""

from __future__ import annotations

import argparse
import json
import os
import re
import sys
from typing import Dict

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark import trace  # noqa: E402
from benchmark.tools import xplane_spans as xs  # noqa: E402

SCOPE = re.compile(r"^lm\.[\w.]+$")


def scope_of(tf_op: str):
    """(innermost ``lm.…`` component, what follows it) of a path."""
    parts = tf_op.split("/")
    for i in range(len(parts) - 1, -1, -1):
        if SCOPE.match(parts[i]):
            return parts[i], "/".join(parts[i + 1:])
    return "unscoped", tf_op


def by_scope(space, module_prefix: str, detail: str = "", top: int = 12):
    device = xs.first_device(space)
    lines = {line.name: line for line in device.lines}
    runs = sorted((s, s + d) for md, s, d in
                  xs._events(device, lines[trace.MODULES_LINE])
                  if md.name.startswith(module_prefix))
    seconds: Dict[str, float] = {}
    ops: Dict[str, Dict[str, list]] = {}
    wanted = re.compile(detail) if detail else None
    stats_of: Dict[int, Dict] = {}
    for md, start, dur in xs._events(device, lines[trace.OPS_LINE]):
        name = trace.short_name(md.name)
        if not xs._inside(runs, start) or name.startswith("while"):
            continue
        if md.id not in stats_of:
            stats_of[md.id] = xs._metadata_stats(device, md)
        scope, tail = scope_of(stats_of[md.id].get("tf_op", ""))
        seconds[scope] = seconds.get(scope, 0.0) + dur / 1e9
        if wanted is not None and wanted.search(scope):
            rec = ops.setdefault(scope, {}).setdefault(
                name, [0, 0.0, tail[-100:]])
            rec[0] += 1
            rec[1] += dur / 1e9
    return {
        "runs": len(runs), "leaf_seconds": sum(seconds.values()),
        "seconds": dict(sorted(seconds.items(), key=lambda kv: -kv[1])),
        "detail": {scope: [[name, n, s, tail] for name, (n, s, tail) in
                           sorted(by_op.items(),
                                  key=lambda kv: -kv[1][1])[:top]]
                   for scope, by_op in ops.items()}}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("xplane")
    ap.add_argument("--module", default="jit__run_step")
    ap.add_argument("--detail", default="")
    ap.add_argument("--top", type=int, default=12)
    args = ap.parse_args()
    print(json.dumps(by_scope(xs.read_space(args.xplane), args.module,
                              args.detail, args.top), indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
