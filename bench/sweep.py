"""Find the knee of an open-loop cell once, on the chip: the highest
offered rate at which the backlog does not grow over a run.

    python3 bench/sweep.py --workload bitnet3b-burst --rates 2,4,6,8 \
        --seconds 30 --seed 5

One set-up, then one window per rate with the cell's mix at that rate.
Prints, per rate, the requests due and finished, the backlog at the end
and its trend over the window's second half, and the latency tails. The
cell's mix file then states 0.8 x the knee as a number; the benchmark's
runs never search for a rate.
"""

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import copy  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench.run import ROOT, prepare  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=5)
    args = ap.parse_args(argv)
    prepare()
    from bench.harness import device, serving, spec, stats, traffic

    cell = spec.load_cell(args.workload, ROOT)
    device.require(cell.chips)
    eng, _ = serving.build(cell, args.seed, traced=False)
    for rate in (float(r) for r in args.rates.split(",")):
        c = copy.copy(cell)
        c.traffic = copy.deepcopy(cell.traffic)
        c.traffic["arrivals"]["rate"] = rate
        eng.reset()
        reqs = traffic.make_requests(c.traffic, c.config["vocab_size"],
                                     args.seed, args.seconds)
        loop, t0, t_end, _, _ = serving.drive(eng, c, reqs, args.seconds)
        tracks = list(loop.tracks.values())

        def backlog(t):
            return sum(1 for x in tracks
                       if x.due <= t and (x.done_t is None or x.done_t > t))

        half = t0 + 0.5 * (t_end - t0)
        ttft = [stats.ttft_s(x.due, x.syncs, t_end) for x in tracks]
        tpot = [g for g in (stats.tpot_s(x.syncs, t_end) for x in tracks)
                if g is not None]
        print(json.dumps({
            "rate": rate, "due": len(tracks),
            "finished": sum(x.done_t is not None for x in tracks),
            "backlog_half": backlog(half), "backlog_end": backlog(t_end),
            "ttft_p50_ms": 1e3 * stats.percentile(ttft, 50),
            "ttft_p95_ms": 1e3 * stats.percentile(ttft, 95),
            "tpot_p95_ms": 1e3 * stats.percentile(tpot, 95) if tpot else None,
            "window_s": t_end - t0}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
