"""Layer 2: compiled-artifact invariant checks for round-block executables.

Generalizes the HLO-text assertions that used to be copy-pasted across
``tests/test_fedsim_fused.py`` / ``tests/test_fedsim_sharded.py`` into one
analyzer. Given a *lowered* round block (``jit(...).lower(...)``), it
compiles it and verifies the standing engine invariants:

- **no host transfers** — no callback/infeed/outfeed markers in the
  lowered StableHLO and no ``xla_python_cpu_callback`` custom-calls in the
  compiled module (metrics ride the scan; PR 8 rule);
- **donation happened** — the compiled module header carries an
  ``input_output_alias`` (donated carry state; checked on *compiled* text
  because the sharded lowering drops the ``tf.aliasing_output`` attribute);
- **rounds live inside the executable** — a ``while`` op is present (the
  scan-over-rounds), so no per-round dispatch can exist;
- **collectives ride the scan** — cross-client exchange sites sit at
  while-depth ≤ 1 (depth 0 = eval epilogue, depth 1 = the round scan
  body); a collective at depth ≥ 2 is inside an inner loop (EM/SGD) and
  re-pays the exchange every iteration (PR 9 rule). Peer gathers are
  additionally capped at one *logical site* per block;
- **no f64** unless x64 is enabled, and nonzero flops.

A *logical site* groups the per-pytree-leaf HLO ops a single ``psum`` /
``all_gather`` expands into (one op per leaf) by their shared
``op_name``/``source_line`` metadata — counting raw ops would make a
6-leaf psum look like six collectives.

``python -m repro.lint.hlo`` builds a tiny simulation and runs the checks
over all six methods on the fused and/or sharded engines (CI's
HLO-invariant stage). Exit codes: 0 clean, 1 violations, 2 usage.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

from repro import compat
from repro.utils.hlo_text import (OP_NAME_RE, Computation,
                                  parse_computations)

# markers checked on the lowered StableHLO text (same set the engine tests
# have always used)
HOST_MARKERS = ("callback", "infeed", "outfeed", "CopyToHost")

_HOST_CUSTOM_CALL = 'custom_call_target="xla_python_cpu_callback'

_COLLECTIVE_RE = re.compile(
    r"\b(all-reduce|all-gather|reduce-scatter|all-to-all|"
    r"collective-permute)(?:-start)?\(")
_SOURCE_LINE_RE = re.compile(r"source_line=(\d+)")
_F64_RE = re.compile(r"\bf64\[")

_KIND = {"all-reduce": "reduce", "all-gather": "gather",
         "reduce-scatter": "reduce_scatter", "all-to-all": "all_to_all",
         "collective-permute": "permute"}


# ----------------------------------------------------- compiled-HLO parsing

def computation_while_depths(comps: Dict[str, Computation]) -> Dict[str, int]:
    """while-nesting depth per computation, from the entry: the round-scan
    body sits at depth 1, an inner lax loop's body at depth ≥ 2."""
    depths: Dict[str, int] = {c.name: 0 for c in comps.values() if c.is_entry}
    changed = True
    while changed:
        changed = False
        for comp in comps.values():
            if comp.name not in depths:
                continue
            base = depths[comp.name]
            for is_loop, callee in comp.edges:
                if callee not in comps:
                    continue
                nd = base + (1 if is_loop else 0)
                if callee not in depths or nd < depths[callee]:
                    depths[callee] = nd
                    changed = True
    return depths


@dataclasses.dataclass(frozen=True)
class CollectiveSite:
    """One logical collective (all per-leaf HLO ops sharing metadata)."""
    kind: str            # reduce / gather / reduce_scatter / ...
    op_name: str         # jaxpr path from metadata ("" if absent)
    source_line: int     # 0 if absent
    computation: str
    while_depth: int
    n_ops: int           # pytree leaves this site expanded into


def collective_sites(compiled_text: str) -> List[CollectiveSite]:
    comps = parse_computations(compiled_text)
    depths = computation_while_depths(comps)
    grouped: Dict[Tuple, List[Tuple[str, int]]] = {}
    for comp in comps.values():
        depth = depths.get(comp.name, 0)
        for i, line in enumerate(comp.lines):
            m = _COLLECTIVE_RE.search(line)
            if not m:
                continue
            kind = _KIND[m.group(1)]
            op = OP_NAME_RE.search(line)
            src = _SOURCE_LINE_RE.search(line)
            if op or src:
                key = (kind, op.group(1) if op else "",
                       int(src.group(1)) if src else 0)
            else:            # no metadata (hand-written HLO): own site
                key = (kind, f"<{comp.name}:{i}>", 0)
            grouped.setdefault(key, []).append((comp.name, depth))
    sites = []
    for (kind, op_name, src_line), ops in sorted(grouped.items()):
        sites.append(CollectiveSite(
            kind=kind, op_name=op_name if not op_name.startswith("<") else "",
            source_line=src_line, computation=ops[0][0],
            while_depth=max(d for _, d in ops), n_ops=len(ops)))
    return sites


# -------------------------------------------------------------- the report

@dataclasses.dataclass
class RoundBlockReport:
    host_markers: Tuple[str, ...]     # markers present in the lowered text
    host_custom_calls: int            # cpu-callback custom-calls (compiled)
    donated: bool                     # input_output_alias present
    has_scan_loop: bool               # a while op exists (the round scan)
    sites: Tuple[CollectiveSite, ...]
    f64_ops: int
    flops: float

    def gather_sites(self) -> List[CollectiveSite]:
        return [s for s in self.sites if s.kind == "gather"]

    def reduce_sites(self) -> List[CollectiveSite]:
        return [s for s in self.sites if s.kind == "reduce"]


def analyze_hlo_text(compiled_text: str, lowered_text: str = "",
                     flops: float = 0.0) -> RoundBlockReport:
    """Text-level analysis (unit-testable on canned HLO)."""
    return RoundBlockReport(
        host_markers=tuple(m for m in HOST_MARKERS if m in lowered_text),
        host_custom_calls=compiled_text.count(_HOST_CUSTOM_CALL),
        donated="input_output_alias={" in compiled_text.replace(" ", ""),
        has_scan_loop="while(" in compiled_text,
        sites=tuple(collective_sites(compiled_text)),
        f64_ops=len(_F64_RE.findall(compiled_text)),
        flops=flops)


def analyze_round_block(lowered) -> RoundBlockReport:
    """Compile a ``.lower(...)``-ed round block and analyze it."""
    compiled = lowered.compile()
    return analyze_hlo_text(
        compiled.as_text(), lowered_text=lowered.as_text(),
        flops=compat.cost_analysis(compiled).get("flops", 0.0))


def check_round_block(report: RoundBlockReport, *,
                      require_donation: bool = True,
                      require_scan: bool = True,
                      require_flops: bool = True,
                      expect_collectives: bool = False,
                      expect_gather: Optional[bool] = None,
                      max_gather_sites: int = 1,
                      allow_f64: Optional[bool] = None) -> List[str]:
    """Return the list of violated invariants (empty = clean)."""
    v: List[str] = []
    if report.host_markers:
        v.append("host transfer markers in lowered text: "
                 + ", ".join(report.host_markers))
    if report.host_custom_calls:
        v.append(f"{report.host_custom_calls} host-callback custom-call(s) "
                 f"in compiled module")
    if require_donation and not report.donated:
        v.append("no input_output_alias: carry state was not donated")
    if require_scan and not report.has_scan_loop:
        v.append("no while op: rounds are not scanned inside the executable")
    if expect_collectives:
        if not report.reduce_sites():
            v.append("expected cross-client all-reduce sites, found none")
    elif report.sites:
        v.append("unexpected collectives in a single-device block: "
                 + ", ".join(f"{s.kind}@depth{s.while_depth}"
                             for s in report.sites))
    gathers = report.gather_sites()
    if expect_gather is not None and bool(gathers) != expect_gather:
        v.append(f"expected {'a' if expect_gather else 'no'} peer gather, "
                 f"found {len(gathers)} site(s)")
    if len(gathers) > max_gather_sites:
        v.append(f"{len(gathers)} gather sites (> {max_gather_sites}): "
                 f"the peer stack must be gathered once per round and "
                 f"reused")
    for s in report.sites:
        if s.while_depth >= 2:
            v.append(f"{s.kind} at while-depth {s.while_depth} "
                     f"(op_name={s.op_name!r}): collective inside an inner "
                     f"loop body — hoist it to the round scan")
    allow = compat.x64_enabled() if allow_f64 is None else allow_f64
    if not allow and report.f64_ops:
        v.append(f"{report.f64_ops} f64 op(s) with x64 disabled")
    if require_flops and not report.flops > 0:
        v.append("cost analysis reports zero flops")
    return v


def assert_round_block(lowered, **expectations) -> RoundBlockReport:
    """Pytest helper: analyze + check, raising AssertionError with every
    violated invariant. Returns the report for extra assertions."""
    report = analyze_round_block(lowered)
    violations = check_round_block(report, **expectations)
    assert not violations, "round-block invariants violated:\n  " + \
        "\n  ".join(violations)
    return report


# ------------------------------------------------------------------- CLI

# which sharded round bodies perform a per-round peer-stack gather
GATHER_METHODS = ("fedamp", "pfedwn")


def _build_sim(sharded: bool, shard_devices: int = 4, n_clients: int = 4):
    import numpy as np

    from repro.configs.paper_cnn import CNNConfig
    from repro.core.fedsim import FederatedSimulation, FedSimConfig
    from repro.data import (dirichlet_partition, make_client_datasets,
                            synthetic_image_dataset, train_test_split)

    mc = CNNConfig(image_size=8, widths=(4,), hidden=16, n_classes=4)
    base = synthetic_image_dataset(0, 600, image_size=8, n_classes=4)
    parts = dirichlet_partition(base.y, n_clients, alpha=0.3, seed=0)
    train = make_client_datasets(
        base, [train_test_split(p, seed=1)[0] for p in parts])
    test = make_client_datasets(
        base, [train_test_split(p, seed=1)[1] for p in parts])
    pm = np.array([True] * (n_clients - 1) + [False])
    p_err = np.linspace(0.0, 0.2, n_clients).astype(np.float32)
    cfg = FedSimConfig(rounds=3, batch_size=16, em_iters=2, em_subset=64,
                       adapt_subset=32, eval_every=2, taps=True,
                       sharded=sharded,
                       shard_devices=shard_devices if sharded else 1)
    return FederatedSimulation(mc, train, test, pm, p_err, cfg)


def _check_engine(engine: str, methods: Sequence[str],
                  shard_devices: int) -> List[str]:
    failures: List[str] = []
    sim = _build_sim(sharded=(engine == "sharded"),
                     shard_devices=shard_devices)
    data, state = sim.staged_data(), sim.initial_state()
    for method in methods:
        if engine == "sharded":
            expectations = dict(expect_collectives=True,
                                expect_gather=method in GATHER_METHODS)
        else:
            expectations = dict(expect_collectives=False)
        report = analyze_round_block(
            sim.block_fn(method).lower(state, data, 3))
        violations = check_round_block(report, **expectations)
        tag = f"{engine}/{method}"
        if violations:
            failures.append(tag)
            for item in violations:
                print(f"FAIL {tag}: {item}")
        else:
            sites = ", ".join(
                f"{s.kind}x{s.n_ops}@d{s.while_depth}" for s in report.sites
            ) or "none"
            print(f"ok   {tag}: donated={report.donated} "
                  f"flops={report.flops:.3g} collectives=[{sites}]")
    return failures


def main(argv: Optional[List[str]] = None) -> int:
    import argparse

    parser = argparse.ArgumentParser(
        prog="python -m repro.lint.hlo",
        description="Check the round-block HLO invariants for all methods "
                    "on the fused/sharded engines.")
    parser.add_argument("--engine", choices=("fused", "sharded", "both"),
                        default="both")
    parser.add_argument("--methods", default=None,
                        help="comma-separated subset (default: all six)")
    parser.add_argument("--devices", type=int, default=4,
                        help="forced host device count for the sharded "
                             "mesh (default 4; must divide 4 clients)")
    try:
        args = parser.parse_args(argv)
    except SystemExit as exc:
        return int(exc.code or 0)

    if args.engine in ("sharded", "both"):
        # must land before the XLA backend initializes (safe: this CLI is
        # the process entry, nothing has touched devices yet)
        import os
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags
                + f" --xla_force_host_platform_device_count={args.devices}"
            ).strip()

    from repro.core.fedsim import METHODS
    methods = METHODS if not args.methods else tuple(
        m.strip() for m in args.methods.split(",") if m.strip())
    unknown = [m for m in methods if m not in METHODS]
    if unknown:
        print(f"unknown method(s): {', '.join(unknown)}")
        return 2

    shard_devices = min(args.devices, 4)
    engines = (("fused", "sharded") if args.engine == "both"
               else (args.engine,))
    failures: List[str] = []
    for engine in engines:
        failures.extend(_check_engine(engine, methods, shard_devices))
    if failures:
        print(f"{len(failures)} block(s) violate the HLO invariants: "
              + ", ".join(failures))
        return 1
    print("all round-block HLO invariants hold")
    return 0


if __name__ == "__main__":
    import sys
    sys.exit(main())
