# One function per paper table/figure. Prints ``name,us_per_call,derived`` CSV.
"""Benchmark harness:

  PYTHONPATH=src python -m benchmarks.run [--only fig5|fig6|kernel|scaling]

fig5    — paper Fig 5 (simulation, p_Y in {0.01, 0.1}) runtime + ratios
fig6    — paper Fig 6 (census-like categorical data) runtime + ratios
kernel  — counting-kernel micro + GFP §3.1 optimization ablation
scaling — distributed engine strong-scaling on an 8-device host mesh
stream  — streaming out-of-core sweep vs single-pass dense counting
serve   — micro-batched count serving vs per-query launches, cold/warm cache
mine    — unified level-wise mining driver vs the legacy per-engine loops
shard   — sharded-store throughput (1/2/4/8 shards) + async flush latency
rules   — minority-rule serving cold/warm throughput + 1/2/4-shard parity
gfp     — GFP-hybrid vs level-wise launches-per-mine on dense long patterns
obs     — telemetry overhead on the warm serve path (metrics off vs on)
"""
import argparse
import sys


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    choices=["fig5", "fig6", "kernel", "scaling", "stream",
                             "serve", "mine", "shard", "rules", "gfp",
                             "obs"])
    args = ap.parse_args()

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    from .common import emit

    suites = {}
    if args.only in (None, "fig5"):
        from . import fig5_sim
        suites["fig5"] = fig5_sim.run
    if args.only in (None, "fig6"):
        from . import fig6_census
        suites["fig6"] = fig6_census.run
    if args.only in (None, "kernel"):
        from . import kernel_bench
        suites["kernel"] = kernel_bench.run
    if args.only in (None, "scaling"):
        from . import scaling
        suites["scaling"] = scaling.run
    if args.only in (None, "stream"):
        from . import streaming
        suites["stream"] = streaming.run
    if args.only in (None, "serve"):
        from . import serve
        suites["serve"] = serve.run
    if args.only in (None, "mine"):
        from . import mine_loop
        suites["mine"] = mine_loop.run
    if args.only in (None, "shard"):
        from . import shard_serve
        suites["shard"] = shard_serve.run
    if args.only in (None, "rules"):
        from . import rule_serve
        suites["rules"] = rule_serve.run
    if args.only in (None, "gfp"):
        from . import gfp_hybrid
        suites["gfp"] = gfp_hybrid.run
    if args.only in (None, "obs"):
        from . import obs_overhead
        suites["obs"] = obs_overhead.run

    print("name,us_per_call,derived")
    ok = True
    for name, fn in suites.items():
        try:
            emit(fn())
        except Exception as e:  # pragma: no cover
            ok = False
            print(f"{name}/SUITE_FAILED,0,{type(e).__name__}:{e}",
                  file=sys.stderr)
    if not ok:
        raise SystemExit(1)


if __name__ == '__main__':
    main()
