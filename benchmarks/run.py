# One function per paper table/figure. Prints ``name,us_per_call,derived`` CSV.
"""Benchmark harness: every paper table/figure, plus kernel microbenches.

  PYTHONPATH=src python -m benchmarks.run                    # all, full size
  PYTHONPATH=src python -m benchmarks.run fig4_1 ...         # subset
  PYTHONPATH=src python -m benchmarks.run --smoke            # tiny shapes,
                                                             # 1 rep, CI-safe
"""

import argparse


def main() -> None:
    from benchmarks import (
        chaos_recovery,
        fig4_1_kernel_breakdown,
        fig5_2_load_fraction,
        fig5_3_transfer,
        fig6_2_kernels,
        pipeline_throughput,
        rounds_makespan,
        serve_latency,
        table6_1_speedup,
    )

    suites = {
        "fig4_1": fig4_1_kernel_breakdown.run,
        "fig5_2": fig5_2_load_fraction.run,
        "fig5_3": fig5_3_transfer.run,
        "table6_1": table6_1_speedup.run,
        "fig6_2": fig6_2_kernels.run,
        "pipeline": pipeline_throughput.run,
        "serve": serve_latency.run,
        "rounds": rounds_makespan.run,
        "chaos": chaos_recovery.run,
    }
    ap = argparse.ArgumentParser()
    ap.add_argument("suites", nargs="*", default=[],
                    help=f"subset of suites (default: all of {', '.join(suites)})")
    ap.add_argument("--suite", action="append", default=[],
                    help="same as the positional form (repeatable): "
                         "python -m benchmarks.run --suite table6_1 --smoke")
    ap.add_argument("--skip", action="append", default=[],
                    help="suites to exclude (repeatable) — lets CI run "
                         "'everything except X' without a hand-maintained list")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny shapes, 1 rep — finishes in well under 2 minutes")
    ap.add_argument("--overlap", choices=["on", "off", "both"], default="both",
                    help="fig5_3: modeled makespan with the boundary/interior "
                         "overlap schedule on/off (delta row when 'both')")
    ap.add_argument("--autotune-cache", default=None,
                    help="fig4_1: path to a repro.kernels.autotune cache "
                         "JSON; the Pallas kernel rows use its block-size "
                         "winners (default: $REPRO_AUTOTUNE_CACHE / "
                         "~/.cache/repro-dg/autotune.json, inline smoke "
                         "sweep when absent)")
    ap.add_argument("--devices", type=int, default=1,
                    help="pipeline: add a sharded-fused row over this many "
                         "devices (needs XLA_FLAGS=--xla_force_host_platform_"
                         "device_count=N on CPU)")
    ap.add_argument("--list-scenarios", action="store_true",
                    help="print every registered arch/scenario and exit")
    args = ap.parse_args()

    if args.list_scenarios:
        from repro.configs.registry import format_listing

        print(format_listing())
        return

    requested = list(args.suites) + list(args.suite)
    unknown = [s for s in requested + args.skip if s not in suites]
    if unknown:
        ap.error(f"unknown suites {unknown}; choose from {list(suites)}")
    picked = [s for s in (requested or list(suites)) if s not in args.skip]
    from repro.launch.compile_cache import use_compile_cache

    use_compile_cache()
    print("name,us_per_call,derived")
    for name in picked:
        kwargs = {"smoke": args.smoke}
        if name == "fig4_1":
            kwargs["autotune_cache"] = args.autotune_cache
        if name == "fig5_3":
            kwargs["overlap"] = args.overlap
        if name == "pipeline":
            kwargs["devices"] = args.devices
        suites[name](**kwargs)


if __name__ == "__main__":
    main()
