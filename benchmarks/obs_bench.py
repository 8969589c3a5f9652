"""Observability benchmark -> BENCH_obs.json (``run.py --only obs``).

The tracer's contract has four measurable halves, and this bench measures
all of them on the same multi-round simulation:

  * overhead — wall time of a fully-traced run vs the identical disabled
    run (claim: <= 3%). Traced/untraced reps run in INTERLEAVED pairs and
    the claim is judged on the median per-pair overhead with its MAD
    spread reported alongside: back-to-back per-arm minima put the two
    arms in different thermal/allocator regimes and once measured the
    "overhead" at -3.7%, i.e. pure noise.
  * completeness — every byte the CommLedger charged is attributable to
    some span (``Tracer.attributed_bytes()`` equals the ledger's totals
    and the ``unattributed`` bucket is empty). ASSERTED, not just
    reported: a wire charge outside any span is an instrumentation bug.
  * fidelity — the traced run's final weights and ledger summary are
    bit-identical to the untraced run's (observing the run must not
    change it).
  * compile discipline — the tracer's ``compile`` spans (JAX's own
    compile events, ``repro.obs.compiles``): every hot-path backend
    compile lands in round 0 of the first traced run; a backend compile
    span whose ancestry reaches a ``round > 0`` span is a
    retrace-per-round bug and fails the bench (claim
    ``zero_hot_path_recompiles_after_round_0``). MetaTraining's eager
    scan recompiles every round, so the bench fails until it is
    compiled once.

Trace throughput (``records_per_sec``) is measured in isolation — a
synthetic span/event storm serialized to a tmpfile — because dividing the
simulation's span count by the whole simulation wall (once ~2 records/s)
says nothing about the tracer; the storm number is what actually bounds
tracer overhead at scale.

Timing uses the repo clock (``repro.obs.timing``); one warmup run per arm
pays jit compiles before anything is timed. The report is written through
``repro.obs.registry.write_bench`` (flcheck OBS002), which also appends
the fingerprinted record to ``experiments/bench_history.jsonl`` for
``python -m repro.obs regress``.
"""
from __future__ import annotations

import os
import tempfile

import numpy as np

from repro import obs
from repro.configs import FLConfig, get_wrn_config
from repro.data import SyntheticImageDataset, partition_k_shards
from repro.fl.simulation import FLSimulation
from repro.models.wrn import make_split_wrn
from repro.obs.registry import write_bench
from repro.obs.timing import monotonic

ROUNDS = 3
NUM_CLIENTS, SAMPLES_PER_CLIENT = 3, 150
REPS = 3                      # interleaved (untraced, traced) pairs
OVERHEAD_CLAIM = 0.03
STORM_SPANS = 20000           # synthetic records for the throughput probe


def _flcfg(**kw):
    base = dict(num_clients=NUM_CLIENTS, clients_per_round=NUM_CLIENTS,
                local_epochs=1, local_batch_size=50, local_lr=0.1,
                pca_components=16, clusters_per_class=3, kmeans_iters=6,
                meta_epochs=10, meta_batch_size=8, meta_lr=0.05)
    base.update(kw)
    return FLConfig(**base)


def _setting():
    cfg = get_wrn_config().reduced()
    model = make_split_wrn(cfg)
    train = SyntheticImageDataset(NUM_CLIENTS * SAMPLES_PER_CLIENT,
                                  image_size=cfg.image_size, num_classes=10,
                                  modes_per_class=3, noise=0.25, seed=0)
    test = SyntheticImageDataset(300, image_size=cfg.image_size,
                                 num_classes=10, modes_per_class=3,
                                 noise=0.25, seed=1)
    clients = partition_k_shards(train, NUM_CLIENTS, k_classes=3,
                                 samples_per_client=SAMPLES_PER_CLIENT,
                                 seed=0)
    return model, clients, test


def _run_once(model, clients, test, observability):
    sim = FLSimulation(model, clients, test,
                       _flcfg(observability=observability), seed=0)
    t0 = monotonic()
    res = sim.run(rounds=ROUNDS, eval_every=ROUNDS)
    return sim, res, monotonic() - t0


def _weights_equal(a, b):
    import jax
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    return len(la) == len(lb) and all(
        bool((np.asarray(x) == np.asarray(y)).all()) for x, y in zip(la, lb))


def _median(xs):
    s = sorted(xs)
    n = len(s)
    return s[n // 2] if n % 2 else 0.5 * (s[n // 2 - 1] + s[n // 2])


def _trace_throughput(n_spans=STORM_SPANS):
    """Isolated tracer throughput: open/close ``n_spans`` spans (one event
    + one byte charge each) and serialize the lot to a tmpfile."""
    tr = obs.Tracer(meta={"synthetic_storm": True})
    t0 = monotonic()
    with obs.use_tracer(tr):
        for i in range(n_spans):
            with obs.span("storm", i=i) as sp:
                obs.event("tick", i=i)
                sp.charge("up", "knowledge", 64, 1)
    fd, path = tempfile.mkstemp(suffix=".jsonl")
    os.close(fd)
    try:
        tr.write_jsonl(path)
    finally:
        os.unlink(path)
    dt = monotonic() - t0
    n_records = len(tr.spans) + len(tr.events)
    return n_records / max(dt, 1e-9), n_records


def _hot_path_compiles(tr):
    """Partition the trace's backend ``compile`` spans by the round of
    their enclosing span's ancestry (None = outside any round: setup,
    meta-training warmup, eval)."""
    by_id = {s.span_id: s for s in tr.spans}

    def round_of(sp):
        pid = sp.parent_id
        while pid is not None and pid in by_id:
            sp = by_id[pid]
            if "round" in sp.attrs:
                return sp.attrs["round"]
            pid = sp.parent_id
        return None

    comp = [s for s in tr.spans
            if s.name == "compile" and s.attrs.get("phase") == "backend"]
    hot = [s for s in comp if (round_of(s) or 0) > 0]
    return comp, hot


def run():
    model, clients, test = _setting()
    # warmups pay jit compiles for both arms (identical jaxprs: the tracer
    # adds no jax operations — that IS the bit-identity claim); the traced
    # warmup's cold trace is what the compile check judges
    _run_once(model, clients, test, False)
    sim_warm, _, _ = _run_once(model, clients, test, True)

    pairs = []
    sim_off = sim_on = res_off = res_on = None
    for _ in range(REPS):
        sim_off, res_off, dt_off = _run_once(model, clients, test, False)
        sim_on, res_on, dt_on = _run_once(model, clients, test, True)
        pairs.append((dt_off, dt_on))
    overheads = [(on - off) / off for off, on in pairs]
    overhead = _median(overheads)
    spread = _median([abs(o - overhead) for o in overheads])   # MAD
    t_off = min(off for off, _ in pairs)
    t_on = min(on for _, on in pairs)

    # fidelity: observing the run must not change it
    bit_identical = _weights_equal(sim_off.server.global_params,
                                   sim_on.server.global_params)
    ledger_equal = res_off.comm == res_on.comm
    assert bit_identical, "traced run diverged from untraced weights"
    assert ledger_equal, "traced run diverged from untraced ledger"

    # completeness: every ledger byte reachable from some span
    tr = sim_on.tracer
    att = tr.attributed_bytes()
    att_up = sum(v for k, v in att.items() if k.startswith("up/"))
    att_down = sum(v for k, v in att.items() if k.startswith("down/"))
    led_up = sum(sim_on.server.ledger.up.values())
    led_down = sum(sim_on.server.ledger.down.values())
    assert att_up == led_up and att_down == led_down, (
        f"span-attributed bytes {att_up}/{att_down} != ledger "
        f"{led_up}/{led_down}")
    assert not tr.unattributed, (
        f"bytes charged outside any span: {dict(tr.unattributed)}")

    # compile check: judged on the FIRST traced run (its rounds > 0 run
    # with warm jit caches, so a backend compile there is a retrace)
    compiles, hot_compiles = _hot_path_compiles(sim_warm.tracer)
    assert not hot_compiles, (
        "hot-path recompiles after round 0: "
        + str([(s.attrs.get("fn"), s.attrs.get("cache_hit"))
               for s in hot_compiles]))
    compile_counters = {
        k: v for k, v in
        sim_warm.tracer.metrics.snapshot()["counters"].items()
        if k.startswith("compile.")}

    n_spans, n_events = len(tr.spans), len(tr.events)
    records_per_sec, storm_records = _trace_throughput()
    sketches = sum(1 for e in tr.events if e["name"] == "selection_sketch")

    report = {
        "rounds": ROUNDS, "clients": NUM_CLIENTS, "reps": REPS,
        "untraced_s": t_off, "traced_s": t_on,
        "overhead_frac": overhead,
        "overhead_spread": spread,
        "overhead_pairs": overheads,
        "spans": n_spans, "events": n_events,
        "selection_sketches": sketches,
        "records_per_sec": records_per_sec,
        "throughput_storm_records": storm_records,
        "attributed_up_bytes": att_up, "attributed_down_bytes": att_down,
        "compile_events_round_0": len(compiles) - len(hot_compiles),
        "compile_counters": compile_counters,
        "phase_wall_s": res_on.phase_wall_s,
        "round_wall_s": res_on.round_wall_s,
        "claims": {
            "overhead_leq_3pct": overhead <= OVERHEAD_CLAIM,
            "every_ledger_byte_span_attributed": True,   # asserted above
            "traced_run_bit_identical": bool(bit_identical and ledger_equal),
            "zero_hot_path_recompiles_after_round_0": not hot_compiles,
        },
    }
    rows = [
        ("obs_untraced_s", t_off, None),
        ("obs_traced_s", t_on, None),
        ("obs_overhead_frac", overhead,
         f"median of {REPS} pairs, MAD {spread:.4f}, <= "
         f"{OVERHEAD_CLAIM} claimed"),
        ("obs_trace_records", float(n_spans + n_events),
         f"{n_spans} spans + {n_events} events"),
        ("obs_records_per_sec", records_per_sec,
         f"synthetic storm, {storm_records} records"),
        ("obs_selection_sketches", float(sketches),
         f"{NUM_CLIENTS} clients x {ROUNDS} rounds"),
        ("obs_compile_events", float(len(compiles)),
         "backend compile spans, all in round 0 / setup"),
    ]
    for claim, ok in report["claims"].items():
        rows.append((f"claim_{claim}", "PASS" if ok else "FAIL", None))

    out = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "BENCH_obs.json")
    write_bench(out, report)
    return rows, report


if __name__ == "__main__":
    for name, val, extra in run()[0]:
        v = f"{val:.4f}" if isinstance(val, float) else val
        print(f"{name},{v},{extra if extra is not None else ''}")
