"""Export the serving ETA model as a self-contained StableHLO artifact.

Reads a msgpack params artifact (``save_model``), AOT-exports the
forward with a symbolic batch dimension, and writes a file the serving
layer can run WITHOUT this package's model code — point
``ETA_MODEL_PATH`` at it and ``EtaService`` serves it (kernel
``stablehlo_aot``). See ``train/checkpoint.export_serving_fn``.

Usage: python scripts/export_model.py [--model artifacts/eta_mlp.msgpack]
       [--out artifacts/eta_forward.stablehlo] [--platforms cpu,tpu] [--cpu]
"""

from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> None:
    parser = argparse.ArgumentParser()
    parser.add_argument("--model", default=None,
                        help="msgpack artifact (default: the serving "
                             "resolution — ETA_MODEL_PATH or the in-repo "
                             "artifact)")
    parser.add_argument("--out", default=None,
                        help="output path (default: <model>.stablehlo)")
    parser.add_argument("--platforms", default="cpu,tpu")
    parser.add_argument("--cpu", action="store_true")
    args = parser.parse_args()
    if args.cpu or os.environ.get("ROUTEST_FORCE_CPU") == "1":
        import jax

        jax.config.update("jax_platforms", "cpu")

    from routest_tpu.train.checkpoint import (default_model_path,
                                              export_serving_fn,
                                              load_exported_serving_fn,
                                              load_model)

    model_path = args.model or default_model_path()
    out = args.out or os.path.splitext(model_path)[0] + ".stablehlo"
    platforms = tuple(p.strip() for p in args.platforms.split(",") if p.strip())

    model, params = load_model(model_path)
    print(f"export: {model_path} (hidden={list(model.hidden)}, "
          f"quantiles={list(model.quantiles)}) → {out} "
          f"platforms={list(platforms)}")
    export_serving_fn(out, model, params, platforms=platforms)

    # Verify before declaring success: reload and compare one batch —
    # unless this machine cannot execute any target platform (e.g.
    # exporting a TPU-only artifact from a CPU box): the artifact is
    # still valid, it just can't be verified here.
    import numpy as np

    from routest_tpu.train.checkpoint import backend_platforms

    if not any(p in platforms for p in backend_platforms()):
        print(f"written: {os.path.getsize(out)} bytes. Backend "
              f"{backend_platforms()[0]} cannot execute platforms "
              f"{list(platforms)} — verification skipped; verify on a "
              f"target machine.")
        return

    from routest_tpu.data.features import batch_from_mapping
    from routest_tpu.data.synthetic import generate_dataset

    exported = load_exported_serving_fn(out)
    x = batch_from_mapping(generate_dataset(64, seed=9))
    forward = model.apply_quantiles_xla if model.quantiles else model.apply_xla
    want = np.asarray(forward(params, x))
    got = np.asarray(exported(x))
    # bf16-trunk models tolerate bf16-scale differences: the exported
    # program and the live jit may pick different (equally valid) dot
    # lowerings for the emulated-bf16 CPU path.
    import jax.numpy as jnp

    tight = model.policy.compute_dtype == jnp.float32
    np.testing.assert_allclose(got, want, rtol=2e-5 if tight else 2e-2,
                               atol=1e-4 if tight else 0.25)
    print(f"verified: {os.path.getsize(out)} bytes, parity on 64 rows OK "
          f"(max rel err {np.max(np.abs(got - want) / np.maximum(want, 1e-6)):.2e})")


if __name__ == "__main__":
    main()
