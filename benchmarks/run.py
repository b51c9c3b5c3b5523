# One function per paper table/figure. Prints ``name,us_per_call,derived`` CSV.
# These are quality experiments run on the CPU; speed is measured on the chip
# by bench/ (BENCHMARK.json).
#
# Modules (see each for the claim it validates):
#   fig1_reconstruction  Figure 1  — coding schemes vs entity count
#   fig3_collisions      Figure 3  — median vs zero LSH threshold
#   table1_gnn           Table 1   — NC/Rand/Hash with 4 GNNs + link pred
#   table2_4_6_memory    Tables 2/4/6 — memory arithmetic (EXACT)
#   table3_merchant      Table 3   — bipartite merchant classification
#   table5_cm_sweep      Table 5   — (c, m) sweep
#   compression_sweep    ISSUE 8   — quality-vs-memory: paper vs hashemb vs tt
#
# Run all:        PYTHONPATH=src python -m benchmarks.run
# Run a subset:   PYTHONPATH=src python -m benchmarks.run --only fig3,table2
# Smoke (CI):     PYTHONPATH=src python -m benchmarks.run --smoke
#                 (~2 steps per benchmark: exercises every module's code path
#                 quickly; emitted numbers are not measurements)
import argparse
import sys
import time
import traceback

MODULES = [
    "table2_4_6_memory",   # instant, exact — first
    "fig3_collisions",
    "fig1_reconstruction",
    "table5_cm_sweep",
    "compression_sweep",
    "table1_gnn",
    "table3_merchant",
]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated module-name substrings")
    ap.add_argument("--smoke", action="store_true",
                    help="run each benchmark for ~2 steps (rot check, not a "
                         "measurement)")
    args = ap.parse_args()

    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()

    if args.smoke:
        from benchmarks import common
        common.SMOKE = True

    print("name,us_per_call,derived")
    failures = 0
    for name in MODULES:
        if args.only and not any(s in name for s in args.only.split(",")):
            continue
        mod = __import__(f"benchmarks.{name}", fromlist=["run"])
        t0 = time.time()
        try:
            mod.run()
            print(f"# {name} done in {time.time() - t0:.1f}s", file=sys.stderr)
        except Exception:
            failures += 1
            print(f"# {name} FAILED:\n{traceback.format_exc()}", file=sys.stderr)
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
