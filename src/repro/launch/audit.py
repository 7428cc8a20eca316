"""HLO contract auditor CLI: trace every registered production path,
run the analysis passes, and gate against the committed baseline.

    PYTHONPATH=src python -m repro.launch.audit                  # gate
    PYTHONPATH=src python -m repro.launch.audit --update-baseline
    PYTHONPATH=src python -m repro.launch.audit --only decode    # subset
    PYTHONPATH=src python -m repro.launch.audit --selftest       # seeded
                                                 # regressions must trip

Exit status: 0 only when every contract holds AND every metric matches
``HLO_CONTRACTS.json`` (bench-gate style — intentional structural change
is re-seeded with ``--update-baseline`` and shows up in review).

``--selftest`` proves the auditor has teeth by seeding the three
regressions the PR 7 acceptance names — a reintroduced barrier
all-gather on the ksharded Y>1 path, a forced int8 -> f32 bounce before
a dot, a non-donated KV-cache decode step — and failing unless each one
trips the matching pass.
"""
import os

if "xla_force_host_platform_device_count" not in os.environ.get(
        "XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=8")
# The lines above MUST run before any jax import (jax locks the device
# count at first init — the dryrun.py rule): the multidev schedule
# contracts need 8 host devices.

import argparse      # noqa: E402
import json          # noqa: E402
import sys           # noqa: E402
from typing import List, Optional  # noqa: E402

_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))
BASELINE = os.path.join(_ROOT, "HLO_CONTRACTS.json")


def _selftest() -> int:
    """Seed the three named regressions; each MUST trip its pass."""
    import jax
    import jax.numpy as jnp

    from repro.analysis import parse_hlo, run_passes
    from repro.core.maxeva_matmul import XYZConfig, schedule_wire_ops

    failures: List[str] = []

    def expect_error(case: str, hlo: str, expect: dict, code: str):
        findings, _ = run_passes(parse_hlo(hlo), expect)
        hits = [f for f in findings
                if f.code == code and f.severity == "error"]
        if hits:
            print(f"audit --selftest: ok   {case}: tripped "
                  f"{hits[0].pass_name}/{code} ({len(hits)} finding(s))")
        else:
            failures.append(case)
            print(f"audit --selftest: FAIL {case}: expected an error "
                  f"finding with code {code}, got "
                  f"{[f.code for f in findings]}")

    # 1. reintroduced barrier all-gather on the ksharded Y>1 path: the
    # pre-overlap implementation gathered the K blocks with a blocking
    # all-gather before the GEMM — the collective-schedule pass must
    # reject it against the overlapped plan's allowed set
    from jax.sharding import PartitionSpec as P
    from repro.launch.mesh import make_mesh

    mesh = make_mesh(2, 4)
    xcfg = XYZConfig(y=2, schedule="reduce_scatter", x_layout="ksharded")

    def barrier_body(x, w):
        xg = jax.lax.all_gather(
            x, "model", axis_index_groups=[[0, 1], [2, 3]], axis=1,
            tiled=True)
        partial = xg @ w
        return jax.lax.psum_scatter(
            partial, "model", scatter_dimension=1,
            axis_index_groups=[[0, 1], [2, 3]], tiled=True)

    fn = jax.jit(jax.shard_map(
        barrier_body, mesh=mesh,
        in_specs=(P("data", "model"), P("model", None)),
        out_specs=P("data", "model"), check_vma=False))
    hlo = fn.lower(
        jax.ShapeDtypeStruct((8, 32), jnp.float32),
        jax.ShapeDtypeStruct((64, 16), jnp.float32)).compile().as_text()
    expect_error(
        "barrier all-gather on ksharded Y>1",
        hlo, {"allowed_collectives": schedule_wire_ops(xcfg, 4)},
        "barrier-all-gather")

    # 2. forced int8 -> f32 bounce before a dot: the naive dequantize-
    # then-float-GEMM implementation
    def bounced(qx, sx, w):
        x = qx.astype(jnp.float32) * sx
        return x @ w

    hlo = jax.jit(bounced).lower(
        jax.ShapeDtypeStruct((4, 64), jnp.int8),
        jax.ShapeDtypeStruct((4, 1), jnp.float32),
        jax.ShapeDtypeStruct((64, 32), jnp.float32)).compile().as_text()
    expect_error("int8 -> f32 bounce before a dot",
                 hlo, {"int8_clean": True}, "int8-bounce")

    # 3. non-donated KV-cache decode step: jit WITHOUT donate_argnums
    # against the production donation contract
    from repro.analysis.contract import _smoke_cfg
    from repro.launch.mesh import make_mesh as mk
    from repro.models.lm import Model

    cfg = _smoke_cfg()
    model = Model(cfg, mk(1, 1))
    aparams = model.abstract_params()
    acache = model.abstract_cache(2, 24)
    tok = jax.ShapeDtypeStruct((2, 1), jnp.int32)
    pos = jax.ShapeDtypeStruct((), jnp.int32)
    hlo = jax.jit(model.decode_step).lower(
        aparams, acache, tok, pos).compile().as_text()
    n_p = len(jax.tree_util.tree_leaves(aparams))
    n_c = len(jax.tree_util.tree_leaves(acache))
    expect_error(
        "non-donated KV-cache decode step",
        hlo, {"donated_params": tuple(range(n_p, n_p + n_c))},
        "non-donated-buffer")

    if failures:
        print(f"audit --selftest: FAIL ({len(failures)}/3 seeded "
              f"regressions not caught: {failures})")
        return 1
    print("audit --selftest: PASS (3/3 seeded regressions caught)")
    return 0


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--baseline", default=BASELINE)
    ap.add_argument("--update-baseline", action="store_true",
                    help="write the traced contract metrics to "
                         "--baseline instead of gating against them")
    ap.add_argument("--only", default=None,
                    help="substring filter on contract names (spot "
                         "checks; the gate always runs everything)")
    ap.add_argument("--allow-device-skips", action="store_true",
                    help="tolerate contracts skipped for lack of "
                         "devices (local spot checks only — the gate "
                         "treats a skip as a coverage regression)")
    ap.add_argument("--selftest", action="store_true",
                    help="seed the three known regressions and verify "
                         "each trips its pass")
    args = ap.parse_args(argv)

    if args.selftest:
        return _selftest()

    from repro.analysis import (diff_baseline, production_contracts,
                                run_contract)
    from repro.analysis.contract import to_baseline

    contracts = production_contracts()
    if args.only:
        contracts = [c for c in contracts if args.only in c.name]
        if not contracts:
            print(f"audit: no contract matches --only {args.only!r}")
            return 2

    reports = []
    for c in contracts:
        r = run_contract(c)
        print(r.format())
        reports.append(r)

    if args.update_baseline:
        if args.only:
            print("audit: refusing --update-baseline with --only (a "
                  "partial baseline would fail every other contract)")
            return 2
        payload = to_baseline(reports)
        with open(args.baseline, "w") as f:
            json.dump(payload, f, indent=2, sort_keys=True)
            f.write("\n")
        print(f"audit: baseline reseeded at {args.baseline} "
              f"({len(payload['contracts'])} contracts)")
        # reseeding never launders an outright violation
        bad = [r for r in reports if r.errors]
        for r in bad:
            print(f"audit: VIOLATION in {r.contract} survives the "
                  f"reseed — fix the program, not the baseline")
        return 1 if bad else 0

    baseline = None
    if os.path.exists(args.baseline):
        with open(args.baseline) as f:
            baseline = json.load(f)
    else:
        print(f"audit: no baseline at {args.baseline}; run with "
              f"--update-baseline to seed it")

    failures, lines = diff_baseline(
        reports, baseline, allow_device_skips=args.allow_device_skips)
    if args.only and baseline is not None:
        # a subset run legitimately misses baseline contracts
        failures = [f for f in failures
                    if not f.startswith("MISSING contract")]
    for line in lines:
        print(f"audit: {line}")
    for f in failures:
        print(f"audit: {f}")
    if failures:
        return 1
    n = len([r for r in reports if not r.skipped])
    print(f"audit: PASS ({n} contracts match the committed baseline)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
