"""Read, on the chip and at the cell's own size, the numbers that decide
``correct`` in a sequence cell (``route-lm-kexaone-mixed`` unless
``--cell`` names another whose driver has ``program_routes``,
``reference`` and ``gaps``): the control's (the reference in float8, put
in the program's place) and the planted faults' (``--faults``: the
module under ``benchmark/`` that holds them, ``faults_kexaone``), each
against ONE computation of the float32 reference for the seed (as
``readings_seq.py`` and ``readings_sala.py``, which this one can stand
in for); the program's own readings come with every ``run.py`` result
(``checks``). The limits in the mix's file are set between these
readings; PERF.md records them.

    python3 benchmark/tools/readings_kexaone.py --seeds 1,2 \\
        --what program,control,fault:window_off_by_one

One JSON line per seed and reading on standard output, and appended to
``chiprun_out/readings_kexaone.jsonl``.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import shutil
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)



def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--what", default="program,control")
    ap.add_argument("--cell", default="route-lm-kexaone-mixed")
    ap.add_argument("--faults", default="faults_kexaone")
    ap.add_argument("--set", action="append", default=[])
    ap.add_argument("--no-chip", action="store_true",
                    help="for a rehearsal at a toy size on the CPU")
    args = ap.parse_args()

    import importlib

    from benchmark import run as R

    faults = importlib.import_module("benchmark." + args.faults).FAULTS

    manifest = R.load_json(R.REPO, "BENCHMARK.json")
    cell, config, mix = R.load_cell(manifest, args.cell)
    R.override(config, mix, args.set)
    if not args.no_chip:
        R.require_chips(int(cell["chips"]))

    from routest_tpu.core.cache import enable_compile_cache

    enable_compile_cache()
    mod = R.load_module("drivers", mix["driver"])
    out_dir = os.path.join(REPO, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)

    def say(seed, what, numbers, t0):
        line = json.dumps({"workload": args.cell, "what": what, "seed": seed,
                           "seconds": round(time.perf_counter() - t0, 1),
                           **numbers})
        print(line, flush=True)
        with open(os.path.join(out_dir, "readings_kexaone.jsonl"), "a") as f:
            f.write(line + "\n")

    for seed in (int(s) for s in args.seeds.split(",")):
        scratch = tempfile.mkdtemp(prefix="routest-readings-")
        try:
            run = R.Run(seed, config, mix, R.REPO, scratch)
            t0 = time.perf_counter()
            sound = mod.Driver(run)
            sound.window(0.0)
            got = sound.program_routes()
            sound.release()
            want = sound.reference()
            # the faults last: each faulty program draws the seed's
            # parameters again, and two sets do not fit the chip
            asked = sorted(args.what.split(","),
                           key=lambda w: w.startswith("fault:"))
            for what in asked:
                t0 = time.perf_counter()
                if what == "program":
                    numbers = sound.gaps(got, want)
                elif what == "control":
                    numbers = sound.gaps(
                        sound.reference(mix["control"]), want)
                else:
                    sound.params = sound.scores = None
                    gc.collect()
                    with faults[what.split(":", 1)[1]]():
                        faulty = mod.Driver(run)
                        faulty.window(0.0)
                    numbers = sound.gaps(faulty.program_routes(), want)
                    faulty.release()
                    faulty.params = faulty.scores = None
                    del faulty
                say(seed, what, numbers, t0)
                gc.collect()
        finally:
            shutil.rmtree(scratch, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
