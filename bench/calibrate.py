"""Readings that the limits of ``correct`` are set from, on the chip.

    python3 bench/calibrate.py --workload <cell> --seeds 12 --controls 3

In one process: for each of ``--seeds`` seeds the program's first call of
the cell (set-up as a run makes it, ``rounds_per_call`` rounds) against the
reference; then for each of ``--controls`` seeds the control
(``reference.control_kwargs``) and every fault planted in the reference
that the cell's method can have (``reference.faults``), each against the
reference. Writes one JSON line
per reading to ``--out``, with the compared numbers and the raw outputs of
both sides, and prints, per number, the largest program reading and the
smallest control and fault readings. The benchmark's own runs never run
this.
"""
import argparse
import gc
import json
import os
import sys
import time

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def _raw(outputs):
    """A call's outputs as JSON: arrays become lists."""
    return [{k: (v.tolist() if hasattr(v, "tolist") else v)
             for k, v in r.items()} for r in outputs]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--controls", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=2**31 + 1000)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path[0:1] = [ROOT, os.path.join(ROOT, "src")]
    import jax
    from bench import compare, harness, reference
    from bench import data as bdata
    from repro import compat, obs
    compat.enable_compilation_cache()

    cat = harness.Catalog()
    wl = cat.workload(args.workload)
    harness.check_devices(wl["chips"])
    cfg, traffic = cat.config(wl["config"]), cat.traffic(wl["traffic"])
    method, rounds = traffic["method"], traffic["rounds_per_call"]
    out_path = args.out or os.path.join(
        ROOT, "bench_out", f"calib_{args.workload}.jsonl")
    os.makedirs(os.path.dirname(out_path), exist_ok=True)
    sink = open(out_path, "a")
    readings = {}

    def emit(kind, seed, got, base, **extra):
        nums = compare.numbers(got, base)
        line = {"cell": args.workload, "kind": kind, "seed": seed,
                "numbers": nums, **extra}
        print(json.dumps(line), flush=True)
        line.update(got=_raw(got), base=_raw(base))
        sink.write(json.dumps(line) + "\n")
        sink.flush()
        readings.setdefault(kind, []).append(nums)

    def ref(fed, key_seed, **kw):
        return reference.Reference(cfg, method, fed, key_seed, **kw).run(
            rounds)

    for i in range(args.seeds):
        seed = args.first_seed + i
        fed = bdata.make_federation(cfg, seed)
        key_seed = bdata.derived_seed(seed)
        with jax.default_matmul_precision(cfg["matmul_precision"]):
            rec = obs.RunRecorder()
            sim = harness.build_sim(cfg, traffic, fed, key_seed, rec)
            t0 = time.perf_counter()
            sim.run(method)
            t_prog = time.perf_counter() - t0
        prog = harness.call_outputs(rec, rec.run_id)
        del sim, rec
        gc.collect()
        t0 = time.perf_counter()
        base = ref(fed, key_seed)
        emit("program", seed, prog, base, program_s=t_prog,
             reference_s=time.perf_counter() - t0)
    for i in range(args.controls):
        seed = args.first_seed + 10_000 + i
        fed = bdata.make_federation(cfg, seed)
        key_seed = bdata.derived_seed(seed)
        base = ref(fed, key_seed)
        kinds = [("control", reference.control_kwargs(cfg))] + [
            (f"fault_{f}", {"fault": f})
            for f in reference.faults(method)]
        for kind, kw in kinds:
            emit(kind, seed, ref(fed, key_seed, **kw), base)
    names = sorted({k for rs in readings.values() for r in rs for k in r})
    for name in names:
        row = {}
        for kind, rs in readings.items():
            vals = [r[name] for r in rs if name in r]
            if vals:
                row[kind] = max(vals) if kind == "program" else min(vals)
        print(f"summary {name}: " + json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
