"""Async FL service launcher: run the event-driven server loop over a
seeded traffic model, optionally under chaos, printing per-run throughput
(ticks/sec, bytes/sec) and the final composed-model accuracy.

  PYTHONPATH=src python -m repro.launch.serve_fl --ticks 6 --traffic poisson \
      --rate 2 --buffer-size 2 --delay-ticks 2
  PYTHONPATH=src python -m repro.launch.serve_fl --sync-check   # oracle mode

``--sync-check`` runs the degenerate configuration (DegenerateTraffic,
buffer == cohort) AND the synchronous ``FLSimulation`` on the same seed,
then asserts the bit-identity contract (weights + ledger) — the CI service
smoke job drives exactly this.
"""
from __future__ import annotations

import argparse

import numpy as np

from repro.obs.timing import monotonic


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--ticks", type=int, default=4)
    ap.add_argument("--traffic", default="degenerate",
                    choices=["degenerate", "poisson", "diurnal"])
    ap.add_argument("--rate", type=float, default=2.0)
    ap.add_argument("--delay-ticks", type=int, default=0)
    ap.add_argument("--period", type=int, default=24)
    ap.add_argument("--buffer-size", type=int, default=0,
                    help="0 = cohort size (the sync-degenerate buffer)")
    ap.add_argument("--alpha", type=float, default=0.5,
                    help="FedBuff staleness exponent")
    ap.add_argument("--clients", type=int, default=4)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--traffic-seed", type=int, default=0)
    ap.add_argument("--drop", type=float, default=0.0,
                    help="client crash rate (chaos wire when > 0)")
    ap.add_argument("--corrupt", type=float, default=0.0,
                    help="frame bit-flip rate (chaos wire when > 0)")
    ap.add_argument("--trace", default="",
                    help="write the span trace JSONL here")
    ap.add_argument("--sync-check", action="store_true",
                    help="degenerate run + FLSimulation; assert bit-identity")
    args = ap.parse_args(argv)

    import jax  # noqa: F401  (force the backend up before timing)
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    from repro.configs import FLConfig, get_wrn_config
    from repro.data import SyntheticImageDataset, partition_k_shards
    from repro.fl.faults import FaultPlan
    from repro.fl.service import (DegenerateTraffic, DiurnalTraffic,
                                  FLService, PoissonTraffic)
    from repro.models.wrn import make_split_wrn

    wrn = get_wrn_config().reduced()
    model = make_split_wrn(wrn)
    train = SyntheticImageDataset(100 * args.clients,
                                  image_size=wrn.image_size, seed=0)
    test = SyntheticImageDataset(100, image_size=wrn.image_size, seed=1)
    clients = partition_k_shards(train, args.clients, k_classes=2,
                                 samples_per_client=40)
    cfg = FLConfig(num_clients=args.clients, clients_per_round=args.clients,
                   local_batch_size=20, pca_components=8,
                   clusters_per_class=3, kmeans_iters=4, meta_epochs=1,
                   meta_batch_size=10,
                   transport_checksum=bool(args.drop or args.corrupt),
                   observability=bool(args.trace))
    plan = None
    if args.drop or args.corrupt:
        plan = FaultPlan(drop_rate=args.drop, bitflip_rate=args.corrupt)

    if args.traffic == "poisson":
        traffic = PoissonTraffic(rate=args.rate, seed=args.traffic_seed,
                                 delay_ticks=args.delay_ticks)
    elif args.traffic == "diurnal":
        traffic = DiurnalTraffic(rate=args.rate, seed=args.traffic_seed,
                                 delay_ticks=args.delay_ticks,
                                 period=args.period)
    else:
        traffic = DegenerateTraffic()

    svc = FLService(model, clients, test, cfg, seed=args.seed,
                    traffic=traffic,
                    buffer_size=args.buffer_size or None,
                    staleness_alpha=args.alpha, fault_plan=plan)
    t0 = monotonic()
    res = svc.run(ticks=args.ticks, drain=(args.traffic != "degenerate"))
    dt = monotonic() - t0
    total_bytes = res.comm.get("total_up", 0) + res.comm.get("total_down", 0)
    acc = res.test_acc[-1] if res.test_acc else float("nan")
    print(f"serve_fl: {args.ticks} ticks, {sum(res.arrivals_per_tick)} "
          f"arrivals, {res.flushes} flushes in {dt:.2f}s "
          f"({args.ticks / max(dt, 1e-9):.2f} ticks/s, "
          f"{total_bytes / max(dt, 1e-9):.0f} B/s)")
    print(f"serve_fl: M_COM acc={acc:.4f}  "
          f"mean staleness={res.mean_staleness:.2f}  "
          f"drops={sum(res.drops)}")
    if args.trace and svc.tracer.enabled:
        svc.tracer.write_jsonl(args.trace)
        print(f"serve_fl: trace -> {args.trace}")

    if args.sync_check and (args.traffic != "degenerate"
                            or args.buffer_size):
        ap.error("--sync-check requires degenerate traffic and the "
                 "default (cohort-sized) buffer")
    if args.sync_check:
        from repro.fl.simulation import FLSimulation
        sim = FLSimulation(model, clients, test, cfg, seed=args.seed,
                           fault_plan=plan)
        sres = sim.run(rounds=args.ticks, eval_every=args.ticks)
        sl = jax.tree_util.tree_leaves(sim.server.global_params)
        vl = jax.tree_util.tree_leaves(svc.server.global_params)
        same_w = all(np.array_equal(np.asarray(a), np.asarray(b))
                     for a, b in zip(sl, vl))
        sim_comm = {k: v for k, v in sres.comm.items()
                    if k != "total_samples"}
        same_l = dict(res.comm) == sim_comm
        print(f"serve_fl: sync-check weights={'OK' if same_w else 'FAIL'} "
              f"ledger={'OK' if same_l else 'FAIL'}")
        if not (same_w and same_l):
            return 1
    print("serve_fl: done")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
