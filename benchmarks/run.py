"""Benchmark runner: one entry per paper table + the transport-layer
communication benchmark + kernel micro-benchmarks + the selection-pipeline
suite. Prints ``name,value,extra`` CSV rows and a paper-claim validation
summary; writes experiments/bench_results.json, BENCH_selection.json (the
§3.1 hot-path trajectory), BENCH_comms.json (bytes-per-round + accuracy
per transport codec), BENCH_faults.json (the chaos sweep: graceful
degradation + recovery overhead under injected faults), BENCH_obs.json
(tracing overhead + byte-attribution completeness) and BENCH_service.json
(async service: sync-equivalence, throughput, accuracy-vs-staleness), all
tracked PR over PR. Schemas: docs/benchmarks.md.

  PYTHONPATH=src python -m benchmarks.run \\
      [--only tables|kernels|comms|selection|faults|analysis|obs|service]
"""
from __future__ import annotations

import argparse
import json
import os
import sys

from repro.compile_cache import enable_compile_cache
from repro.obs.timing import monotonic


def _emit(rows):
    for name, val, extra in rows:
        v = f"{val:.4f}" if isinstance(val, float) else val
        print(f"{name},{v},{extra if extra is not None else ''}")


def run_tables(results):
    import jax
    from benchmarks import paper_tables as T
    t0 = monotonic()
    all_claims = {}

    def section(title, key, fn):
        # clear_caches between sections: the XLA CPU JIT dylib cache can
        # fail ("Failed to materialize symbols") after many executables
        jax.clear_caches()
        print(f"# {title}")
        out = fn()   # a failed table fails the run
        rows, claims = out[0], out[1]
        _emit(rows)
        all_claims.update(claims)
        results[key] = rows
        return out

    section("Table 2/8 — selection vs full metadata", "table_2_8",
            T.table_2_and_8_selection_vs_full)
    section("Table 3 — meta-training hyperparameters", "table_3",
            T.table_3_hyperparameters)
    section("Table 4 — number of clusters", "table_4",
            T.table_4_cluster_count)
    out = section("Table 5/6 + Fig 2 — overfitting on selected subset, L2",
                  "table_5_6", T.table_5_6_overfitting_and_l2)
    results["fig2_curves"] = {str(k): v for k, v in out[2].items()}
    section("Table 7 — L2 in FL meta-training", "table_7", T.table_7_l2_in_fl)

    print(f"\n# paper-claim validation ({monotonic()-t0:.0f}s)")
    ok = 0
    for claim, passed in all_claims.items():
        print(f"claim,{'PASS' if passed else 'FAIL'},{claim}")
        ok += bool(passed)
    results["claims"] = {c: bool(p) for c, p in all_claims.items()}
    print(f"claims_passed,{ok}/{len(all_claims)},")
    return all_claims


def run_comm(results):
    """Byte-true communication benchmark over the transport layer: bytes
    per round and final accuracy per codec (raw_f32/f16/int8) plus the
    Table-2 upload-everything baseline -> BENCH_comms.json."""
    from benchmarks import comm_bench as C
    print("# Communication (transport codecs, exact wire bytes) "
          f"-> BENCH_comms.json ({C.NUM_CLIENTS} clients x "
          f"{C.SAMPLES_PER_CLIENT} samples, {C.ROUNDS} rounds/codec)")
    rows, report = C.run()
    _emit(rows)
    results["comms"] = report
    return report


def run_faults(results):
    """Chaos benchmark over the fault-tolerant runtime: accuracy, bytes
    (first transmission vs. retransmit/duplicate overhead) and injected-
    vs-detected corruption counts per (drop, corrupt) rate point
    -> BENCH_faults.json."""
    from benchmarks import chaos_bench as F
    print("# Fault tolerance (deterministic chaos sweep, CRC32 wire) "
          f"-> BENCH_faults.json ({F.NUM_CLIENTS} clients x "
          f"{F.SAMPLES_PER_CLIENT} samples, {F.ROUNDS} rounds/point)")
    rows, report = F.run()
    _emit(rows)
    results["faults"] = report
    return report


def run_service(results):
    """Async service benchmark: sync-equivalence vs FLSimulation,
    throughput (rounds/sec, bytes/sec) and the accuracy-vs-staleness
    curve -> BENCH_service.json."""
    from benchmarks import service_bench as V
    print("# async FL service (degenerate oracle + staleness sweep) "
          f"-> BENCH_service.json ({V.NUM_CLIENTS} clients x "
          f"{V.SAMPLES_PER_CLIENT} samples, {V.ROUNDS} rounds)")
    rows, report = V.run()
    _emit(rows)
    results["service"] = report
    return report


def run_obs(results):
    """Observability benchmark: tracing overhead (traced vs disabled),
    byte-attribution completeness (asserted) and trace throughput
    -> BENCH_obs.json."""
    from benchmarks import obs_bench as O
    print("# observability (tracer overhead + completeness) "
          f"-> BENCH_obs.json ({O.NUM_CLIENTS} clients x "
          f"{O.SAMPLES_PER_CLIENT} samples, {O.ROUNDS} rounds/arm)")
    rows, report = O.run()
    _emit(rows)
    results["obs"] = report
    return report


def run_selection(results):
    """§3.1 selection pipeline at paper scale -> BENCH_selection.json."""
    from benchmarks import selection_bench as S
    print("# selection pipeline (2500 maps, 10x10 clusters; seed vs fused)")
    rows, report = S.run()
    _emit(rows)
    results["selection"] = report
    return report


def run_analysis_bench(results):
    """flcheck wall time: embedded self-test fixtures + the full src/
    scan. The scan must stay under 10 s so the CI gate stays cheap."""
    from repro.analysis import run_analysis
    from repro.analysis.selftest import FIXTURES, run_self_test
    print("# static analysis (flcheck self-test + full src/ scan)")
    t0 = monotonic()
    failures = run_self_test()
    t_self = monotonic() - t0
    t0 = monotonic()
    findings = run_analysis(["src", "benchmarks"])
    t_scan = monotonic() - t0
    rows = [
        ("analysis_selftest_s", t_self,
         f"{len(FIXTURES) - len(failures)}/{len(FIXTURES)} fixtures ok"),
        ("analysis_scan_s", t_scan,
         f"{len(findings)} finding(s), budget 10s"),
        ("analysis_scan_under_budget", float(t_scan < 10.0), "PASS if 1"),
        ("analysis_selftest_ok", float(not failures), "PASS if 1"),
    ]
    _emit(rows)
    results["analysis"] = {"selftest_s": t_self, "scan_s": t_scan,
                           "fixtures": len(FIXTURES),
                           "fixture_failures": failures,
                           "findings": len(findings)}
    return rows


def run_kernels(results):
    from benchmarks import kernel_bench as K
    print("# kernel micro-benchmarks (jnp oracle on CPU + v5e roofline est.)")
    rows = []
    rows += K.bench_kmeans()
    rows += K.bench_selection_pipeline()
    rows += K.bench_attention()
    rows += K.bench_decode()
    _emit([(n, v, e) for n, v, e in rows])
    results["kernels"] = rows


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    choices=[None, "tables", "kernels", "comm", "comms",
                             "selection", "faults", "analysis", "obs",
                             "service"])
    args = ap.parse_args(argv)
    enable_compile_cache()

    results = {}
    t0 = monotonic()
    if args.only in (None, "selection"):
        run_selection(results)
    if args.only in (None, "comm", "comms"):
        run_comm(results)
    if args.only in (None, "faults"):
        run_faults(results)
    if args.only in (None, "obs"):
        run_obs(results)
    if args.only in (None, "service"):
        run_service(results)
    if args.only in (None, "kernels"):
        run_kernels(results)
    if args.only in (None, "analysis"):
        run_analysis_bench(results)
    claims = {}
    if args.only in (None, "tables"):
        claims = run_tables(results)

    os.makedirs("experiments", exist_ok=True)
    with open("experiments/bench_results.json", "w") as f:
        json.dump(results, f, indent=1, default=str)
    print(f"\ntotal,{monotonic()-t0:.1f}s,results->experiments/bench_results.json")
    if claims and not all(claims.values()):
        failed = [c for c, p in claims.items() if not p]
        print(f"WARNING: {len(failed)} claim(s) not validated: {failed}")


if __name__ == "__main__":
    main()
