"""One benchmark run of one cell, on the machine it is started on.

    python3 bench/run.py --workload bitnet3b-batch --seed 7 --seconds 40 --trace 0

Loads the cell named in ``BENCHMARK.json``, makes the weights from the seed
on the chip, warms up, measures for ``--seconds``, checks the served tokens
against the plain reference, and prints one JSON line last on stdout:
the end-to-end metrics with ``--trace 0``, the per-layer metrics and the
trace's busy and idle time with ``--trace 1``. Without a TPU it exits
non-zero and prints no result.
"""

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def prepare():
    """Before JAX is imported: the compile cache at the program's fixed
    ``<checkout>/.jax_cache`` (every program cached, however quick to
    compile), and the checkout's packages on the path."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    os.environ["JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES"] = "0"
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be non-negative")
    prepare()

    from bench.harness import serving, spec

    cell = spec.load_cell(args.workload, ROOT)
    result, _, _ = serving.run_cell(
        cell, args.seed, args.seconds, traced=bool(args.trace),
        t_process=T_PROCESS,
        log=lambda m: print(m, file=sys.stderr, flush=True))
    for name, c in result["check"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
