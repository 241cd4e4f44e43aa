"""The benchmark's one command.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Runs one cell of ``BENCHMARK.json`` on the machine it is started on and
prints, as the last line of standard output, one JSON object: ``correct``,
``attempted`` and ``failed`` rounds, the cell's end-to-end metrics (or with
``--trace 1`` its per-layer metrics, the device's busy and window seconds
and a breakdown), the device, and last the numbers compared with the
reference beside their limits. It needs an accelerator with as many chips
as the cell asks for, and exits 2 with no result line where there is none.
JAX's compilation cache lives in ``.jax_cache/`` of the checkout.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path[0:1] = [ROOT, os.path.join(ROOT, "src")]
    from bench import harness
    from repro import compat
    compat.enable_compilation_cache()
    try:
        result = harness.run_cell(args.workload, args.seed, args.seconds,
                                  bool(args.trace), t_start=T_START)
    except harness.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
