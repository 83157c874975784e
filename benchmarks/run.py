"""Benchmark harness aggregator — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV. Heavy benchmarks cache their
results under results/bench/; pass --force (or REPRO_BENCH_FORCE=1) to
recompute, --only <substr> to run a subset.
"""

from __future__ import annotations

import argparse
import os
import sys

BENCHES = [
    ("hb_schedule", "bench_hb_schedule"),               # Table 1
    ("fidelity_correlation", "bench_fidelity_correlation"),  # Fig 1b / 5b
    ("end_to_end", "bench_end_to_end"),                 # Fig 3a/3d
    ("cross_benchmark", "bench_cross_benchmark"),       # Fig 3b/3e
    ("cold_start", "bench_cold_start"),                 # Fig 3c/3f
    ("generalization", "bench_generalization"),         # Fig 4
    ("mfo_ablation", "bench_mfo_ablation"),             # Fig 5a
    ("sc_ablation", "bench_sc_ablation"),               # Fig 6a/6b
    ("alpha_sensitivity", "bench_alpha_sensitivity"),   # Fig 6c
    ("warmstart", "bench_warmstart"),                   # Table 3
    ("overhead", "bench_overhead"),                     # §7.4.4
    ("roofline", "bench_roofline"),                     # §Roofline (ours)
    ("batch_eval", "bench_batch_eval"),                 # batched engine (ours)
    ("surrogate", "bench_surrogate"),                   # packed forest plane (ours)
    ("config_space", "bench_config_space"),             # columnar space plane (ours)
    ("compression", "bench_compression"),               # batched Shapley plane (ours)
    ("pool_scaling", "bench_pool_scaling"),             # fused propose step (ours)
]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--force", action="store_true",
                    default=os.environ.get("REPRO_BENCH_FORCE") == "1")
    ap.add_argument("--only", type=str, default=None)
    ap.add_argument("--trace", action="store_true",
                    help="persist a Perfetto trace per tuning run under "
                         "results/bench/traces/ (inspect with scripts/inspect_run.py)")
    args = ap.parse_args()

    if args.trace:
        from .common import CACHE

        os.environ["REPRO_BENCH_TRACE_DIR"] = os.path.join(CACHE, "traces")

    import importlib

    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    print("name,us_per_call,derived")
    failures = 0
    for name, mod_name in BENCHES:
        if args.only and args.only not in name:
            continue
        try:
            mod = importlib.import_module(f"benchmarks.{mod_name}")
            rows = mod.run(force=args.force)
        except Exception as e:  # keep the harness running
            print(f"{name},0,ERROR {type(e).__name__}: {e}")
            failures += 1
            continue
        for r in rows:
            derived = str(r["derived"]).replace(",", ";")
            print(f"{r['name']},{r['us_per_call']:.1f},{derived}")
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
