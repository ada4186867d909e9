"""Per-path, per-bucket curves for the compiled scoring artifact.

Three serving paths, head-to-head at every batch bucket the serving
layer actually flushes:

- **xla** — the jit forward (the reference path), device per-iteration
  cost via the same ``lax.fori_loop`` slope method as bench.py (the
  fixed dispatch cost would otherwise swamp a sub-ms step);
- **pallas** — the fused kernel (``ops/fused_mlp.py``) with a tile
  sweep per batch, compiled. Without a TPU the script refuses to run;
  ``--cpu`` asks for the interpreter explicitly and writes a
  non-binding selection record;
- **aot** — the per-bucket ``jit().lower().compile()`` serving entry:
  measured as WALL time per single call (dispatch included — the whole
  point of AOT is what the fori_loop slope hides), against the jit
  call's wall time at the same bucket.

Plus fused-vs-unfused quantile-head rows (``quantile_heads`` vs the
scan-form ``quantile_heads_unfused`` epilogue) so the head-fusion claim
has a measured number on every host.

Writes TWO artifacts:
- ``artifacts/serving_kernel.json`` — the full per-path record (this
  bench's own curve, re-recorded at HEAD);
- ``artifacts/kernel_bench.json`` — the serving-selection win table
  (``serve/ml_service.py:_fused_selection`` reads it; only a TPU run
  can enable the kernel).

``--gate`` exits nonzero if the Pallas path loses at
any bucket the PREVIOUS record claimed it wins — the "fused ≥ XLA at
its win buckets" regression check.

Usage: python scripts/bench_serving_kernel.py [--quick] [--cpu] [--gate]
       [--batches 8 64 512 1024 2048 4096 32768 131072]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
import warnings

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def main() -> None:
    parser = argparse.ArgumentParser()
    parser.add_argument("--batches", type=int, nargs="+",
                        default=[8, 64, 512, 1024, 2048, 4096, 32768,
                                 131072])
    parser.add_argument("--repeats", type=int, default=3)
    parser.add_argument("--tiles", type=int, nargs="+",
                        default=[512, 2048, 4096],
                        help="kernel batch-tile candidates (clamped to the "
                             "row-padded batch, deduped, per batch size; "
                             "the kernel rejects tiles above its MAX_TILE)")
    parser.add_argument("--cpu", action="store_true",
                        help="hermetic CPU run (interpreter-mode kernel; "
                             "the selection record will not enable serving)")
    parser.add_argument("--quick", action="store_true",
                        help="small batches + 1 repeat: the CI guardband "
                             "configuration (tests/test_serving_kernel_"
                             "bench.py)")
    parser.add_argument("--no-pallas", action="store_true",
                        help="skip the Pallas rows (interpret mode is "
                             "minutes-slow at large batches on CPU)")
    parser.add_argument("--gate", action="store_true",
                        help="exit 2 if the kernel now loses at a bucket "
                             "the previous record claimed it wins")
    parser.add_argument("--out", default=os.path.join(
        REPO, "artifacts", "serving_kernel.json"))
    args = parser.parse_args()
    if args.quick:
        args.batches = [8, 512, 4096]
        args.repeats = 1
    if args.cpu:
        import jax

        jax.config.update("jax_platforms", "cpu")

    import jax
    import jax.numpy as jnp
    import numpy as np

    from routest_tpu.core.cache import enable_compile_cache
    from routest_tpu.data.features import batch_from_mapping
    from routest_tpu.data.synthetic import generate_dataset
    from routest_tpu.models.eta_mlp import (EtaMLP, quantile_heads,
                                            quantile_heads_unfused)
    from routest_tpu.ops import (fused_eta_forward, pack_eta_params,
                                 resolve_kernel_dtype)
    from routest_tpu.train.checkpoint import default_model_path, load_model

    enable_compile_cache()
    backend = jax.default_backend()
    if backend != "tpu" and not args.cpu:
        sys.exit(f"bench_serving_kernel: needs a TPU, found backend "
                 f"{backend!r} (--cpu records the interpreter-mode "
                 f"structural curve instead)")
    interpret = backend != "tpu"
    run_pallas = not args.no_pallas

    prior_wins = _prior_win_buckets()

    try:
        model, params = load_model(default_model_path())
    except Exception:
        model = EtaMLP()
        params = model.init(jax.random.PRNGKey(0))
    params = jax.device_put(params)
    n_q = len(getattr(model, "quantiles", ()) or ())
    dtype = resolve_kernel_dtype(model)
    packed = jax.device_put(pack_eta_params(model, params, dtype=dtype))
    forward_xla = (model.apply_quantiles if n_q else model.apply)

    data = generate_dataset(max(args.batches), seed=7)
    x_all = np.asarray(batch_from_mapping(data), np.float32)

    def make_runner(forward, batch):
        @jax.jit
        def run(xx, n_iters):
            def body(_, carry):
                xx, _out = carry
                out = forward(xx)
                eta0 = out[:, 0] if out.ndim == 2 else out
                return xx.at[:, 10].add(eta0 * 1e-12), eta0

            return jax.lax.fori_loop(
                0, n_iters, body, (xx, jnp.zeros((batch,), jnp.float32)))

        return run

    def measure(forward, batch) -> float:
        """Per-iteration seconds via the short/long slope."""
        x = jax.device_put(jnp.asarray(x_all[:batch]))
        run = make_runner(forward, batch)
        # Small batches need long loops for the slope to rise above
        # timer noise; keep total device time ~comparable per size.
        # CPU hosts get ~16× shorter loops: the XLA CPU step is ~ms
        # scale, so TPU-sized loops would cost an hour per curve while
        # adding nothing over the ~2% noise floor the guardbands allow.
        budget = (1 << 22) if backend == "tpu" else (1 << 18)
        n_short = max(8, min(400, budget // max(batch, 1)))
        n_long = 4 * n_short
        if args.quick:
            n_short, n_long = max(4, n_short // 8), max(16, n_long // 8)

        def timed(n):
            t0 = time.perf_counter()
            _, eta = run(x, n)
            np.asarray(eta[:1])
            return time.perf_counter() - t0

        timed(2)
        slopes = []
        for _ in range(args.repeats):
            slopes.append((timed(n_long) - timed(n_short))
                          / (n_long - n_short))
        return max(float(np.median(slopes)), 1e-9)

    def wall_per_call(fn, x, calls=20) -> float:
        """Median wall seconds per single dispatch (python overhead
        INCLUDED — this is the number AOT exists to shrink)."""
        fn(x)  # warm / compile
        samples = []
        for _ in range(max(3, args.repeats)):
            t0 = time.perf_counter()
            for _ in range(calls):
                np.asarray(fn(x))
            samples.append((time.perf_counter() - t0) / calls)
        return float(np.median(samples))

    # ── per-path rows ─────────────────────────────────────────────────
    jit_forward = jax.jit(forward_xla)
    rows = []
    for batch in args.batches:
        row = {"batch": batch}
        xla_s = measure(lambda xx: forward_xla(params, xx), batch)
        row["xla_us"] = round(xla_s * 1e6, 1)
        row["xla_mpreds_s"] = round(batch / xla_s / 1e6, 2)

        # AOT vs jit dispatch at this bucket (wall time per call).
        xb = np.ascontiguousarray(x_all[:batch])
        with warnings.catch_warnings():
            warnings.filterwarnings(
                "ignore", message="Some donated buffers were not usable")
            compiled = jax.jit(forward_xla, donate_argnums=(1,)).lower(
                params, jax.ShapeDtypeStruct((batch, xb.shape[1]),
                                             np.float32)).compile()
        calls = max(3, min(30, (1 << 17) // max(batch, 1)))
        row["jit_call_us"] = round(wall_per_call(
            lambda v: jit_forward(params, v), xb, calls) * 1e6, 1)
        row["aot_call_us"] = round(wall_per_call(
            lambda v: compiled(params, v), xb, calls) * 1e6, 1)
        row["aot_mpreds_s"] = round(
            batch / (row["aot_call_us"] / 1e6) / 1e6, 2)
        row["dispatch_saved_us"] = round(
            row["jit_call_us"] - row["aot_call_us"], 1)

        # Pallas tile sweep (the serving-selection measurement).
        if run_pallas:
            cap = ((batch + 7) // 8) * 8
            tiles = sorted({min(t, cap) for t in args.tiles})
            # A tile the compiler refuses raises: every tile the kernel's
            # own bound admits must compile.
            pal_s, pal_tile = min(
                (measure(lambda xx: fused_eta_forward(
                    packed, xx, n_q=n_q, tile=t, interpret=interpret),
                    batch), t) for t in tiles)
            row.update({
                "pallas_us": round(pal_s * 1e6, 1),
                "pallas_mpreds_s": round(batch / pal_s / 1e6, 2),
                "pallas_tile": pal_tile,
                "winner": "pallas" if pal_s < xla_s else "xla",
                "speedup": round(xla_s / pal_s, 2),
            })
        rows.append(row)
        print("  batch {:>7,}: xla {:>8} us ({} Mpreds/s) | aot call "
              "{:>8} us (jit {} us) | pallas {}".format(
                  batch, row["xla_us"], row["xla_mpreds_s"],
                  row["aot_call_us"], row["jit_call_us"],
                  row.get("pallas_us", "skipped")), flush=True)

    # ── fused vs unfused quantile heads (any host) ────────────────────
    heads = None
    if n_q:
        def fwd_with(epilogue):
            def f(xx):
                out, dist = model._trunk(params, xx)
                return epilogue(out, dist, n_q)
            return f

        hb = min(16384, max(args.batches))
        fused_s = measure(fwd_with(quantile_heads), hb)
        unfused_s = measure(fwd_with(quantile_heads_unfused), hb)
        heads = {
            "batch": hb,
            "quantiles": n_q,
            "fused_us": round(fused_s * 1e6, 1),
            "unfused_us": round(unfused_s * 1e6, 1),
            "fused_mpreds_s": round(hb / fused_s / 1e6, 2),
            "unfused_mpreds_s": round(hb / unfused_s / 1e6, 2),
            "fused_over_unfused": round(unfused_s / fused_s, 3),
        }
        print(f"  quantile heads @ {hb:,}: fused {heads['fused_us']} us "
              f"vs unfused {heads['unfused_us']} us "
              f"({heads['fused_over_unfused']}x)", flush=True)

    # ── selection win table (same contract as before) ─────────────────
    win_max = 0
    for row in sorted(rows, key=lambda r: r["batch"]):
        if row.get("winner") == "pallas":
            win_max = row["batch"]
        else:
            break
    record = {
        "backend": backend,
        "interpret_mode": interpret,
        "quantiles": n_q,
        "kernel_dtype": dtype,
        "quick": bool(args.quick),
        "cpu_count": os.cpu_count(),
        "rows": rows,
        "quantile_heads": heads,
        "pallas_wins_max_bucket": win_max if backend == "tpu" else 0,
        "recorded_unix": int(time.time()),
    }
    if backend != "tpu":
        # Structural caveat, PR-4 style: a CPU record must be
        # self-describing about what it can and cannot bind.
        record["caveat"] = (
            "CPU host: pallas rows are interpreter-mode (non-binding for "
            "serving selection); xla/aot rows measure the XLA CPU "
            "backend on this box, not the TPU production path")
    out_dir = os.path.dirname(args.out)
    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(record, f, indent=2)
    print(f"serving-kernel record → {args.out}")
    if not args.quick:
        selection = {k: record[k] for k in
                     ("backend", "interpret_mode", "quantiles",
                      "kernel_dtype", "rows", "pallas_wins_max_bucket",
                      "recorded_unix")}
        sel_path = os.path.join(REPO, "artifacts", "kernel_bench.json")
        with open(sel_path, "w") as f:
            json.dump(selection, f, indent=2)
        print(f"pallas_wins_max_bucket={record['pallas_wins_max_bucket']}"
              f" → {sel_path}")

    if args.gate and backend == "tpu" and prior_wins:
        fresh = {r["batch"]: r.get("winner") for r in rows}
        regressed = [b for b in prior_wins
                     if fresh.get(b) not in (None, "pallas")]
        if regressed:
            print(f"GATE FAIL: pallas lost at previously-won buckets "
                  f"{regressed}", file=sys.stderr)
            sys.exit(2)
        print("gate ok: fused ≥ XLA at its recorded win buckets")


def _prior_win_buckets():
    """Buckets the existing selection record claims the kernel wins —
    read BEFORE this run overwrites the record."""
    try:
        with open(os.path.join(REPO, "artifacts",
                               "kernel_bench.json")) as f:
            rec = json.load(f)
        if rec.get("backend") != "tpu":
            return []
        return [int(r["batch"]) for r in rec.get("rows", ())
                if isinstance(r, dict) and r.get("winner") == "pallas"]
    except Exception:
        return []


if __name__ == "__main__":
    main()
