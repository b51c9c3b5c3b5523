"""Readings the limits and the serving rate are set from, many runs in one
process (the chip's set-up is paid once).

    python3 -m bench.readings --workload sage-products.train \\
        --seeds 1,2,3 --systems program,control,fault_half --seconds 2
    python3 -m bench.readings --workload merchant.serve-zipf \\
        --seeds 7 --rates 150,200,250 --seconds 10

Each (rate, seed, system) is one run of ``bench.run`` with the timed path
replaced as ``system`` says: ``program`` (the system under test),
``control`` (the reference in its place at three bfloat16 passes) or a
planted fault (``fault_frozen``, ``fault_half``, ``fault_altered``).  One
JSON line per run: its checks, its end-to-end metrics and its diagnostics.
The benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import contextlib
import io
import json
import sys
import traceback


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--systems", default="program")
    ap.add_argument("--rates", default="")
    ap.add_argument("--seconds", type=float, default=2.0)
    args = ap.parse_args()
    from bench import run
    rates = [float(r) for r in args.rates.split(",") if r] or [None]
    for rate in rates:
        for seed in [int(s) for s in args.seeds.split(",")]:
            for system in args.systems.split(","):
                over = {"traffic": {"rate_per_s": rate}} if rate else None
                err = io.StringIO()
                try:
                    with contextlib.redirect_stderr(err), contextlib.redirect_stdout(io.StringIO()):
                        line = run.main(["--workload", args.workload, "--seed", str(seed),
                                         "--seconds", str(args.seconds), "--trace", "0"],
                                        system=system, overrides=over)
                    diag = [l for l in err.getvalue().splitlines() if l.startswith("[bench]")]
                    out = {"rate": rate, "seed": seed, "system": system,
                           "correct": line["correct"],
                           "checks": {k: c["value"] for k, c in line["checks"].items()},
                           "metrics": {k: m["value"] for k, m in line["metrics"].items()},
                           "diag": diag}
                except Exception as e:      # noqa: BLE001 — a crashed control is a reading too
                    out = {"rate": rate, "seed": seed, "system": system,
                           "error": f"{type(e).__name__}: {e}",
                           "trace": traceback.format_exc()[-1500:]}
                print(json.dumps(out), flush=True)


if __name__ == "__main__":
    sys.exit(main())
