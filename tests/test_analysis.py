"""rtpulint engine tests: per-rule fixtures (true positives at exact
file:line + documented false-positive guards), suppression/baseline
semantics, and the tier-1 whole-repo gate with its runtime budget.

Fixture corpora are synthetic repos under tmp_path (a ``routest_tpu/``
tree + ``docs/*.md``) so every rule is exercised against KNOWN line
numbers, independent of the real package's drift state. The final
tests run the full rule set over the real repo: the gate must be clean
at HEAD and stay under its time budget so the engine can't quietly
become the slowest tier-1 item.
"""

import json
import os
import textwrap
import time

import pytest

from routest_tpu.analysis import all_rules, analyze, load_corpus
from routest_tpu.analysis.engine import load_baseline


def make_repo(tmp_path, files, docs=None):
    """files: {relpath-under-routest_tpu: source}; docs: {name: text}."""
    for rel, text in files.items():
        p = tmp_path / "routest_tpu" / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(textwrap.dedent(text))
    (tmp_path / "docs").mkdir(exist_ok=True)
    for name, text in (docs or {}).items():
        (tmp_path / "docs" / name).write_text(textwrap.dedent(text))
    return load_corpus(str(tmp_path))


def run(corpus, *rules):
    return analyze(corpus, rules=list(rules), use_baseline=False)


def keys(result):
    return [(f.file, f.line) for f in result.findings]


# ---------------------------------------------------------------------------
# Invariant lints

def test_silent_except_exact_line_and_narrow_guard(tmp_path):
    corpus = make_repo(tmp_path, {"m.py": """\
        def f():
            try:
                g()
            except Exception:
                pass

        def ok():
            try:
                g()
            except OSError:
                pass  # narrow: swallowing a specific cleanup error is policy
    """})
    result = run(corpus, "silent-except")
    assert keys(result) == [("routest_tpu/m.py", 4)]


def test_bare_print_exact_line_and_method_guard(tmp_path):
    corpus = make_repo(tmp_path, {"m.py": """\
        def f(doc):
            print("status")
            doc.print()          # a method named print is not the builtin
            s = "print this"     # strings don't trip an AST rule
    """})
    result = run(corpus, "bare-print")
    assert keys(result) == [("routest_tpu/m.py", 2)]


def test_broad_except_unlogged_and_its_loud_guards(tmp_path):
    corpus = make_repo(tmp_path, {"m.py": """\
        def bad():
            try:
                g()
            except Exception:
                return None

        def uses_exc(self):
            try:
                g()
            except Exception as e:
                self._error = str(e)   # error propagated into state

        def logs(log):
            try:
                g()
            except Exception:
                log.warning("g_failed")

        def counts(m):
            try:
                g()
            except Exception:
                m.inc()

        def reraises():
            try:
                g()
            except Exception:
                raise RuntimeError("wrapped")
    """})
    result = run(corpus, "broad-except-unlogged")
    assert keys(result) == [("routest_tpu/m.py", 4)]


def test_blocking_call_under_lock_exact_line(tmp_path):
    corpus = make_repo(tmp_path, {"m.py": """\
        import time

        def f(self):
            with self._lock:
                snapshot = dict(self.state)
                time.sleep(0.5)
            return snapshot

        def g(self, sock):
            with self.cache_lock:
                sock.sendall(b"x")
    """})
    result = run(corpus, "blocking-call-under-lock")
    assert keys(result) == [("routest_tpu/m.py", 6), ("routest_tpu/m.py", 11)]


def test_blocking_call_release_in_finally_is_not_flagged(tmp_path):
    # Documented false-positive guard (lexical rule): the
    # acquire/try/finally-release pattern releases the lock via
    # `lock.release()` — no `with <lock>:` body encloses the sleep, so
    # the rule stays silent rather than guessing hold ranges.
    corpus = make_repo(tmp_path, {"m.py": """\
        import time

        def f(lock):
            lock.acquire()
            try:
                x = 1
            finally:
                lock.release()
            time.sleep(0.5)   # lock already released: fine
    """})
    result = run(corpus, "blocking-call-under-lock")
    assert result.findings == []


def test_thread_unmanaged_and_both_guards(tmp_path):
    corpus = make_repo(tmp_path, {"m.py": """\
        import threading

        def bad():
            t = threading.Thread(target=work)
            t.start()

        def daemonized():
            threading.Thread(target=work, daemon=True).start()

        def joined():
            t = threading.Thread(target=work)
            t.start()
            t.join()
    """})
    result = run(corpus, "thread-unmanaged")
    assert keys(result) == [("routest_tpu/m.py", 4)]
    assert result.findings[0].severity == "warning"


# ---------------------------------------------------------------------------
# JAX hazards

def test_jit_impure_host_call_decorator_and_call_form(tmp_path):
    corpus = make_repo(tmp_path, {"m.py": """\
        import time
        import jax
        from functools import partial

        @jax.jit
        def decorated(x):
            return x * time.time()

        @partial(jax.jit, static_argnums=(1,))
        def partial_form(x, n):
            return x + time.monotonic()

        def call_form(x):
            import numpy as np
            return x * np.random.random()

        fast = jax.jit(call_form)

        def host_side(x):
            return x * time.time()   # not jitted: fine
    """})
    result = run(corpus, "jit-impure-host-call")
    assert keys(result) == [("routest_tpu/m.py", 7),
                            ("routest_tpu/m.py", 11),
                            ("routest_tpu/m.py", 15)]


def test_jit_host_pull_on_traced_arg(tmp_path):
    corpus = make_repo(tmp_path, {"m.py": """\
        import jax
        import numpy as np

        @jax.jit
        def f(x, table):
            host = np.asarray(x)
            return host.sum()

        @jax.jit
        def ok(x):
            local = make()
            return np.asarray(local)   # not a traced parameter
    """})
    result = run(corpus, "jit-host-pull")
    assert keys(result) == [("routest_tpu/m.py", 6)]


def test_jit_donated_reuse_and_rebind_guard(tmp_path):
    corpus = make_repo(tmp_path, {"m.py": """\
        import jax

        def serve(buf, k):
            compiled = jax.jit(score, donate_argnums=(0,))
            out = compiled(buf, k)
            total = buf.sum()
            return out, total

        def rebound(buf, k):
            compiled = jax.jit(score, donate_argnums=(0,))
            buf = compiled(buf, k)
            return buf.sum()   # rebound to the result: fine
    """})
    result = run(corpus, "jit-donated-reuse")
    assert keys(result) == [("routest_tpu/m.py", 6)]


# ---------------------------------------------------------------------------
# Drift detectors

CONFIG_SRC = """\
    KNOWN_KNOBS = {
        "RTPU_DECLARED_KNOB": "a declared knob",
    }
"""


def test_env_knob_undeclared(tmp_path):
    corpus = make_repo(tmp_path, {
        "core/config.py": CONFIG_SRC,
        "serve/m.py": """\
            import os

            def f(env):
                a = os.environ.get("RTPU_DECLARED_KNOB")
                b = env.get("RTPU_GHOST_KNOB")
                return a, b
        """,
    }, docs={"API.md": "RTPU_DECLARED_KNOB RTPU_GHOST_KNOB"})
    result = run(corpus, "env-knob-undeclared")
    assert keys(result) == [("routest_tpu/serve/m.py", 5)]
    assert "RTPU_GHOST_KNOB" in result.findings[0].message


def test_env_knob_undeclared_ignores_docstring_mentions(tmp_path):
    corpus = make_repo(tmp_path, {
        "core/config.py": CONFIG_SRC,
        "serve/m.py": '''\
            """Mentions RTPU_PROSE_ONLY_KNOB in prose — not a read."""

            def f():
                return 1
        ''',
    })
    result = run(corpus, "env-knob-undeclared")
    assert result.findings == []


def test_env_knob_undocumented(tmp_path):
    corpus = make_repo(tmp_path, {
        "core/config.py": CONFIG_SRC + (
            '    import os\n'
            '    UNDOC = os.environ.get("RTPU_UNDOCUMENTED_KNOB")\n'),
    }, docs={"ARCHITECTURE.md": "| `RTPU_DECLARED_KNOB` | documented |"})
    result = run(corpus, "env-knob-undocumented")
    assert len(result.findings) == 1
    assert "RTPU_UNDOCUMENTED_KNOB" in result.findings[0].message
    assert result.findings[0].file == "routest_tpu/core/config.py"


def test_metric_undocumented_exact_line(tmp_path):
    corpus = make_repo(tmp_path, {"obs/m.py": """\
        def setup(reg):
            a = reg.counter("rtpu_documented_total", "fine")
            b = reg.gauge(
                "rtpu_ghost_gauge", "missing from the doc")
            return a, b
    """}, docs={"OBSERVABILITY.md": "| `rtpu_documented_total` | counter |"})
    result = run(corpus, "metric-undocumented")
    assert keys(result) == [("routest_tpu/obs/m.py", 4)]
    assert "rtpu_ghost_gauge" in result.findings[0].message


def test_metric_stale_doc_and_exposition_suffix_guard(tmp_path):
    corpus = make_repo(tmp_path, {"obs/m.py": """\
        def setup(reg):
            return reg.histogram("rtpu_real_seconds", "registered")
    """}, docs={"OBSERVABILITY.md": """\
        `rtpu_real_seconds` and its exposition `rtpu_real_seconds_bucket`
        samples are fine; `rtpu_phantom_total` names nothing.
    """})
    result = run(corpus, "metric-stale-doc")
    assert keys(result) == [("docs/OBSERVABILITY.md", 2)]
    assert "rtpu_phantom_total" in result.findings[0].message


def test_api_route_undocumented_and_param_prefix_guard(tmp_path):
    corpus = make_repo(tmp_path, {"serve/app.py": """\
        ROUTES = [
            "/api/known",
            "/api/known/<item_id>",
            "/api/secret",
        ]
    """}, docs={"API.md": "| `POST /api/known` | and `/api/known/<id>` |"})
    result = run(corpus, "api-route-undocumented")
    assert keys(result) == [("routest_tpu/serve/app.py", 4)]
    assert "/api/secret" in result.findings[0].message


def test_chaos_point_undocumented_including_fstring_prefix(tmp_path):
    corpus = make_repo(tmp_path, {"serve/m.py": """\
        from routest_tpu.chaos import inject

        def f(rid):
            inject("store.http")
            inject("ghost.boundary")
            inject(f"ghost.perreplica.{rid}")
    """}, docs={"ROBUSTNESS.md": "| `store.http` | documented |"})
    result = run(corpus, "chaos-point-undocumented")
    assert keys(result) == [("routest_tpu/serve/m.py", 5),
                            ("routest_tpu/serve/m.py", 6)]


def test_chaos_point_collision_across_modules(tmp_path):
    corpus = make_repo(tmp_path, {
        "serve/a.py": """\
            from routest_tpu.chaos import inject

            def f():
                inject("shared.point")
        """,
        "serve/b.py": """\
            from routest_tpu.chaos import inject

            def g():
                inject("shared.point")
        """,
    }, docs={"ROBUSTNESS.md": "`shared.point`"})
    result = run(corpus, "chaos-point-collision")
    assert keys(result) == [("routest_tpu/serve/b.py", 4)]


# ---------------------------------------------------------------------------
# Change-ledger kinds ↔ LEDGER_KINDS + docs

_LEDGER_STUB = """\
    LEDGER_KINDS = {
        "model.swap": "verified serving swap",
        "live.flip": "live-metric epoch flip",
    }

    def record_change(kind, **kwargs):
        pass
"""


def test_ledger_kind_unregistered_both_call_forms(tmp_path):
    corpus = make_repo(tmp_path, {
        "obs/ledger.py": _LEDGER_STUB,
        "serve/x.py": """\
            from routest_tpu.obs.ledger import record_change

            def f():
                record_change("model.swap", detail={"generation": 1})
                record_change("model.retired_kind")
                record_change(kind="live.flip")
        """,
    }, docs={"OBSERVABILITY.md":
             "`model.swap` `live.flip` `model.retired_kind`"})
    result = run(corpus, "ledger-kind-unregistered")
    assert keys(result) == [("routest_tpu/serve/x.py", 5)]


def test_ledger_kind_undocumented_exact_line(tmp_path):
    corpus = make_repo(tmp_path, {
        "obs/ledger.py": _LEDGER_STUB,
        "serve/x.py": """\
            from routest_tpu.obs.ledger import record_change

            def f():
                record_change("model.swap")
                record_change("live.flip")
        """,
    }, docs={"OBSERVABILITY.md": "## Change ledger\n\n`model.swap`"})
    result = run(corpus, "ledger-kind-undocumented")
    assert keys(result) == [("routest_tpu/serve/x.py", 5)]


def test_ledger_kind_stale_doc_scans_table_rows_only(tmp_path):
    corpus = make_repo(tmp_path, {
        "obs/ledger.py": _LEDGER_STUB,
        "serve/x.py": """\
            from routest_tpu.obs.ledger import record_change

            def f():
                record_change("model.swap")
        """,
    }, docs={"OBSERVABILITY.md": """\
        # Observability

        ## Change ledger & incident correlation

        Events cross regions on the `rtpu.changes` channel.

        | kind | meaning |
        | --- | --- |
        | `model.swap` | verified swap |
        | `model.retired` | gone from the code |

        ## Next section
    """})
    result = run(corpus, "ledger-kind-stale-doc")
    # only the table row with the unregistered kind fires; the prose
    # mention of the bus channel does not.
    assert keys(result) == [("docs/OBSERVABILITY.md", 10)]


# ---------------------------------------------------------------------------
# Suppressions & baseline semantics

def test_suppression_same_line_and_line_above(tmp_path):
    corpus = make_repo(tmp_path, {"m.py": """\
        def f():
            try:
                g()
            except Exception:  # rtpulint: disable=silent-except -- boot probe, failure means not-ready
                pass

        def h():
            try:
                g()
            # rtpulint: disable=silent-except -- standalone comment covers the next line
            except Exception:
                pass
    """})
    result = run(corpus, "silent-except")
    assert result.findings == []
    assert len(result.suppressed) == 2


def test_suppression_for_another_rule_does_not_apply(tmp_path):
    corpus = make_repo(tmp_path, {"m.py": """\
        def f():
            try:
                g()
            except Exception:  # rtpulint: disable=bare-print -- wrong rule id
                pass
    """})
    result = run(corpus, "silent-except")
    assert keys(result) == [("routest_tpu/m.py", 4)]


def test_suppression_without_reason_is_ignored_and_reported(tmp_path):
    corpus = make_repo(tmp_path, {"m.py": """\
        def f():
            try:
                g()
            except Exception:  # rtpulint: disable=silent-except
                pass
    """})
    result = run(corpus, "silent-except")
    rules = {(f.rule, f.line) for f in result.findings}
    assert ("silent-except", 4) in rules     # NOT suppressed
    assert ("bad-suppression", 4) in rules   # and the waiver is flagged


def test_baseline_grandfathers_exact_key_and_requires_reason(tmp_path):
    corpus = make_repo(tmp_path, {"m.py": """\
        def f():
            try:
                g()
            except Exception:
                pass
    """})
    good = tmp_path / "baseline.json"
    good.write_text(json.dumps([{"rule": "silent-except",
                                 "file": "routest_tpu/m.py", "line": 4,
                                 "reason": "grandfathered: pre-engine code"},
                                {"rule": "silent-except",
                                 "file": "routest_tpu/gone.py", "line": 1,
                                 "reason": "stale entry"}]))
    result = analyze(corpus, rules=["silent-except"],
                     baseline_path=str(good))
    assert result.findings == []
    assert len(result.baselined) == 1
    assert [e.file for e in result.stale_baseline] == ["routest_tpu/gone.py"]
    assert result.gate_ok

    bad = tmp_path / "bad.json"
    bad.write_text(json.dumps([{"rule": "silent-except",
                                "file": "routest_tpu/m.py", "line": 4,
                                "reason": ""}]))
    result = analyze(corpus, rules=["silent-except"],
                     baseline_path=str(bad))
    assert result.baseline_errors          # reason is mandatory
    assert not result.gate_ok


def test_checked_in_baseline_entries_all_carry_reasons():
    entries, errors = load_baseline()
    assert errors == []
    assert all(e.reason.strip() for e in entries)


# ---------------------------------------------------------------------------
# Seeded violations of every family, one synthetic repo (the
# acceptance-criteria matrix: each caught at its exact file:line).

def test_seeded_violation_matrix(tmp_path):
    corpus = make_repo(tmp_path, {
        "core/config.py": CONFIG_SRC,
        "serve/seeded.py": """\
            import os
            import time
            import jax

            def undeclared_knob(env):
                return env.get("RTPU_SEEDED_GHOST_KNOB")        # line 6

            def silent():
                try:
                    g()
                except Exception:                                # line 11
                    pass

            def sleepy(self):
                with self._lock:
                    time.sleep(1.0)                              # line 16

            @jax.jit
            def frozen_clock(x):
                return x * time.time()                           # line 20

            def metrics(reg):
                return reg.counter("rtpu_seeded_ghost_total")    # line 23
        """,
    }, docs={"OBSERVABILITY.md": "no families here",
             "API.md": "RTPU_SEEDED_GHOST_KNOB mentioned so only the "
                       "undeclared rule fires"})
    result = analyze(corpus, rules=[
        "env-knob-undeclared", "silent-except", "blocking-call-under-lock",
        "jit-impure-host-call", "metric-undocumented"],
        use_baseline=False)
    got = {(f.rule, f.file, f.line) for f in result.findings}
    seeded = "routest_tpu/serve/seeded.py"
    assert got == {
        ("env-knob-undeclared", seeded, 6),
        ("silent-except", seeded, 11),
        ("blocking-call-under-lock", seeded, 16),
        ("jit-impure-host-call", seeded, 20),
        ("metric-undocumented", seeded, 23),
    }


# ---------------------------------------------------------------------------
# The tier-1 whole-repo gate + budget

def test_whole_repo_gate_is_clean_and_fast():
    """Every rule over the whole package: zero unbaselined findings at
    HEAD, every baseline entry reasoned, and the run bounded so the
    engine can't quietly become the slowest tier-1 item."""
    t0 = time.perf_counter()
    corpus = load_corpus()
    result = analyze(corpus)
    elapsed = time.perf_counter() - t0
    assert result.files_scanned >= 80          # the real package, not a stub
    assert len(result.rules_run) >= 15
    diagnostics = "\n".join(f.format() for f in result.findings)
    assert result.findings == [], f"rtpulint gate:\n{diagnostics}"
    assert result.baseline_errors == []
    for e in result.stale_baseline:
        pytest.fail(f"stale baseline entry: {e.rule} {e.file}:{e.line}")
    assert elapsed < 10.0, (
        f"whole-repo analysis took {elapsed:.1f}s (budget 10s): profile "
        f"the newest rule — one parse per file is the contract")


def test_cli_gate_exits_zero_and_json_shape(capsys):
    from routest_tpu.analysis.__main__ import main

    assert main(["--gate"]) == 0
    assert main(["--gate", "--json"]) == 0
    out = capsys.readouterr().out
    payload = json.loads(out[out.index("{"):])
    assert payload["gate_ok"] is True
    assert payload["files_scanned"] >= 80

    assert main(["--rule", "no-such-rule"]) == 2


def test_rule_catalog_metadata():
    rules = all_rules()
    for rule in rules.values():
        assert rule.severity in ("error", "warning")
        assert rule.description and rule.hint
    # The families the tentpole promises all exist.
    for rid in ("silent-except", "bare-print", "broad-except-unlogged",
                "blocking-call-under-lock", "thread-unmanaged",
                "jit-impure-host-call", "jit-host-pull",
                "jit-donated-reuse", "env-knob-undeclared",
                "env-knob-undocumented", "metric-undocumented",
                "metric-stale-doc", "api-route-undocumented",
                "chaos-point-undocumented", "chaos-point-collision",
                "ledger-kind-unregistered", "ledger-kind-undocumented",
                "ledger-kind-stale-doc", "bad-suppression"):
        assert rid in rules, rid


# ---------------------------------------------------------------------------
# The arrow between the program and what measures it points one way

# Records under artifacts/ the package may name: the CPU-baseline golden,
# the training reports of committed models, and the two frozen records
# placement and the efficiency watchdog read (ROADMAP D3).
_ARTIFACT_RECORDS = {
    "baseline.json", "training_report.json", "transformer_report.json",
    "gnn_report.json", "gnn_report_osm.json", "gnn_report_manila.json",
    "fleet_chips.json", "serving_kernel.json"}


def test_the_package_reads_no_benchmark():
    """No module of the package imports a benchmark, a script or the
    bring-up proof, and none names a record under artifacts/ beyond
    the allowed ones: what measures the program imports the program,
    never the other way."""
    import ast
    import re

    outside = {"bench", "benchmark", "scripts", "chip_smoke"}
    imports, records = [], []
    for src in load_corpus().files:
        for node in src.nodes():
            if isinstance(node, ast.Import):
                mods = [a.name for a in node.names]
            elif isinstance(node, ast.ImportFrom) and not node.level:
                mods = [node.module or ""]
            else:
                mods = []
            imports += [(src.relpath, node.lineno, m) for m in mods
                        if m.split(".")[0] in outside]
            # os.path.join(..., "artifacts", "<name>.json")
            if isinstance(node, ast.Call):
                consts = [a.value for a in node.args
                          if isinstance(a, ast.Constant)
                          and isinstance(a.value, str)]
                if "artifacts" in consts:
                    records += [(src.relpath, c) for c in consts
                                if c.endswith(".json")]
        records += [(src.relpath, m + ".json") for m in
                    re.findall(r"artifacts/([\w.*{},-]+)\.json", src.text)]
    assert not imports, imports
    assert not [r for r in records if r[1] not in _ARTIFACT_RECORDS], records
