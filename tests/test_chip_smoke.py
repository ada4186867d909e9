"""Contract of chip_smoke.py where there is no chip: the parent stays
off JAX, the first child — a device check — fails within seconds and
before any server boots, and no success line is printed."""

import json
import os
import subprocess
import sys
import textwrap
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")

# Runs chip_smoke.py's parent IN this child process, then reports what
# the parent itself imported.
_PARENT = textwrap.dedent("""
    import json, runpy, sys
    smoke, out = sys.argv[1:]
    sys.argv = [smoke, "--out", out]
    try:
        runpy.run_path(smoke, run_name="__main__")
        code = 0
    except SystemExit as e:
        code = e.code
    print(json.dumps({"code": code,
                      "imported": sorted(m for m in sys.modules if m in
                                         ("jax", "jaxlib", "routest_tpu",
                                          "numpy"))}))
""")


def test_help_lists_the_chips_option():
    proc = subprocess.run([sys.executable, SMOKE, "--help"],
                          capture_output=True, text=True, timeout=60)
    assert proc.returncode == 0
    assert "--chips" in proc.stdout and "--seed" in proc.stdout
    assert "--phase" not in proc.stdout     # the children's own switch


def test_no_chip_fails_fast_and_the_parent_never_imports_jax(tmp_path):
    out = tmp_path / "out"
    t0 = time.time()
    proc = subprocess.run([sys.executable, "-c", _PARENT, SMOKE, str(out)],
                          capture_output=True, text=True, cwd=REPO,
                          timeout=120)
    elapsed = time.time() - t0
    lines = [json.loads(line) for line in proc.stdout.splitlines()
             if line.startswith("{")]
    parent = lines[-1]
    assert parent["imported"] == [], parent    # the parent holds no chip
    assert parent["code"] not in (0, None)
    # the device check failed, named, before anything else ran
    assert lines[0]["phase"] == "device" and lines[0]["ok"] is False
    assert "TPU" in lines[0]["error"]
    assert lines[-2] == {"ok": False, "failed_phase": "device",
                         "error": lines[0]["error"]}
    assert not any(line.get("ok") is True for line in lines)
    assert elapsed < 60, elapsed
    assert sorted(os.listdir(out)) == ["device.json"]   # no server log
