"""Readings behind a cell's correctness limit, on the chip.

    python3 bench/control.py --workload bitnet3b-batch --seeds 1,2,3 \
        --control-seeds 1,2,3 --seconds 12

For each seed, one run of the cell as ``bench/run.py`` makes it (set-up,
window, the check against the plain reference), all in one process so the
set-up's programs compile once. On the control seeds the int4 control (the
reference with int4 per-row activations at every projection input) takes
the program's place in the same check: at each served position the token
it puts first is checked, and ``correct`` is the control's (the program's
own gap is printed beside it). Prints one JSON line per seed. The
benchmark's own runs never run the control.
"""

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench.run import ROOT, prepare  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=12.0)
    args = ap.parse_args(argv)
    prepare()
    from bench.harness import serving, spec

    cell = spec.load_cell(args.workload, ROOT)
    controls = {int(s) for s in args.control_seeds.split(",") if s}
    for seed in (int(s) for s in args.seeds.split(",")):
        t = time.perf_counter()
        result, _, check = serving.run_cell(
            cell, seed, args.seconds, traced=False, t_process=t,
            control=seed in controls,
            log=lambda m: print(m, file=sys.stderr, flush=True))
        print(json.dumps({"workload": cell.name, "seed": seed,
                          "correct": result["correct"],
                          "attempted": result["attempted"],
                          "failed": result["failed"], **check,
                          "metrics": {k: v["value"] for k, v in
                                      result["metrics"].items()},
                          "memory_peak_bytes":
                              result["device"]["memory_peak_bytes"],
                          "seconds": time.perf_counter() - t}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
