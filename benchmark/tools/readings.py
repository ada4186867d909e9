"""Read, on the chip and at a cell's own size, the numbers that decide
``correct``: the program's over many seeds (the lower reading), the
control's (the reference in the next lower precision, put in the
program's place) and the planted faults' (``benchmark/faults.py``).
The limits in the traffic files are set between these readings;
PERF.md records them.

    python3 benchmark/tools/readings.py --workload od-score \\
        --what program --seeds 1,2,3 [--seconds 1]

``--what`` is ``program``, ``control`` or ``fault:<name>``. One JSON
line per seed on standard output.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import tempfile
import shutil

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)


def read_one(mod, run, what: str, seconds: float):
    """``what`` "all" reads the program and every candidate control on
    one set-up: {"program": …, "<control>": …}."""
    driver = mod.Driver(run)
    kind = run.mix["driver"]
    if kind == "table_scan":
        driver.window(seconds)
        if what == "control":
            return driver.numbers(answers_precision=run.mix["control"])
        if what == "all":
            return {"program": driver.numbers(),
                    "int8": driver.numbers(answers_precision="int8"),
                    "fp8": driver.numbers(answers_precision="fp8")}
        return driver.numbers()
    if what == "control":
        return mod.gaps(driver.follow(dtype_name=run.mix["control"]),
                        driver.follow())
    if what == "all":
        got, want = driver.program_readings(), driver.follow()
        truth = driver.follow(matmul_precision="highest")
        bf16 = driver.follow(dtype_name="bfloat16")
        return {"program": mod.gaps(got, want),
                "program_vs_highest": mod.gaps(got, truth),
                "bfloat16": mod.gaps(bf16, want),
                "bfloat16_vs_highest": mod.gaps(bf16, truth),
                "default_vs_highest": mod.gaps(want, truth),
                "losses": got["losses"], "ref_losses": want["losses"]}
    return driver.numbers()


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--what", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=1.0)
    ap.add_argument("--set", action="append", default=[])
    ap.add_argument("--no-chip", action="store_true",
                    help="for a rehearsal at a toy size on the CPU")
    args = ap.parse_args()

    from benchmark import faults
    from benchmark import run as R

    manifest = R.load_json(R.REPO, "BENCHMARK.json")
    cell, config, mix = R.load_cell(manifest, args.workload)
    R.override(config, mix, args.set)
    if not args.no_chip:
        R.require_chips(int(cell["chips"]))

    from routest_tpu.core.cache import enable_compile_cache

    enable_compile_cache()
    mod = R.load_module("drivers", mix["driver"])
    for seed in (int(s) for s in args.seeds.split(",")):
        scratch = tempfile.mkdtemp(prefix="routest-readings-")
        try:
            run = R.Run(seed, config, mix, R.REPO, scratch)
            if args.what.startswith("fault:"):
                with faults.FAULTS[args.what.split(":", 1)[1]]():
                    numbers = read_one(mod, run, args.what, args.seconds)
            else:
                numbers = read_one(mod, run, args.what, args.seconds)
        finally:
            shutil.rmtree(scratch, ignore_errors=True)
        print(json.dumps({"workload": args.workload, "what": args.what,
                          "seed": seed, **numbers}), flush=True)
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
