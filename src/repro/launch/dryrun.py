import os
os.environ["XLA_FLAGS"] = (os.environ.get("_REPRO_EXTRA_XLA", "") +
                           " --xla_force_host_platform_device_count=" +
                           os.environ.get("_REPRO_DRYRUN_DEVICES", "512")
                           ).strip()
# ^ MUST precede any jax import: jax locks the device count on first init.

# Multi-pod dry-run: .lower().compile() every (architecture x input shape)
# on the production mesh; report memory_analysis / cost_analysis / collective
# schedule -> EXPERIMENTS.md §Dry-run and §Roofline.
#
# Usage:
#   PYTHONPATH=src python -m repro.launch.dryrun --arch llama3.2-1b --shape train_4k
#   PYTHONPATH=src python -m repro.launch.dryrun --all [--multipod] [--out experiments/dryrun]
#   ... --smoke   (tiny mesh + reduced configs: the CI path)

import argparse
import dataclasses
import json
import sys
import traceback

import jax
import jax.numpy as jnp

from repro.configs import ARCHS, INPUT_SHAPES, TrainConfig, get_config
from repro.launch import mesh as mesh_lib
from repro.launch.hlo_analysis import parse_hlo
from repro.launch.specs import input_specs
from repro.obs import profile
from repro.launch.steps import (make_decode_step, make_prefill_step,
                                make_train_step)
from repro.models.registry import count_params
from repro.obs.timing import monotonic


def resolve_mode(cfg, shape_name: str):
    """(runnable?, force_swa, reason) — DESIGN.md §5 long_500k policy."""
    if shape_name != "long_500k":
        return True, False, ""
    mode = cfg.long_context_mode
    if mode == "skip":
        return False, False, f"{cfg.name}: long_500k outside family envelope"
    if mode in ("native", "state"):
        return True, False, ""
    return True, True, "swa-variant"   # dense archs: sliding-window variant


def build(cfg, shape, mesh, tcfg: TrainConfig, cache_seq_shard=False):
    _, force_swa, _ = resolve_mode(cfg, shape.name)
    if shape.kind == "train":
        step, lm = make_train_step(cfg, tcfg)
    elif shape.kind == "prefill":
        step, lm = make_prefill_step(cfg, force_swa=force_swa)
    else:
        step, lm = make_decode_step(cfg, force_swa=force_swa)
    specs = input_specs(cfg, shape, mesh, tcfg, force_swa=force_swa, lm=lm,
                        cache_seq_shard=cache_seq_shard)
    out_shardings = None
    if specs["mode"] == "train":
        args = (specs["params"], specs["opt_state"], specs["batch"],
                specs["key"])
        # round output = next round's client params: same sharding as input
        pshard = jax.tree.map(lambda s: s.sharding, specs["params"],
                              is_leaf=lambda x: isinstance(
                                  x, jax.ShapeDtypeStruct))
        out_shardings = (pshard, (), None)
    elif specs["mode"] == "prefill":
        args = (specs["params"], specs["batch"])
    else:
        args = (specs["params"], specs["cache"], specs["tokens"])
        cshard = jax.tree.map(lambda s: s.sharding, specs["cache"],
                              is_leaf=lambda x: isinstance(
                                  x, jax.ShapeDtypeStruct))
        out_shardings = (None, cshard)
    return step, args, specs, out_shardings


def run_one(arch: str, shape_name: str, *, multi_pod=False, smoke=False,
            tcfg: TrainConfig = None, save_dir=None, tag="",
            mla_absorbed=False, cache_seq_shard=False, verbose=True):
    tcfg = tcfg or TrainConfig()
    cfg = get_config(arch)
    if smoke:
        cfg = cfg.reduced()
    if mla_absorbed:
        cfg = dataclasses.replace(cfg, mla_absorbed=True)
    shape = INPUT_SHAPES[shape_name]
    if smoke:
        shape = dataclasses.replace(
            shape, seq_len=min(shape.seq_len, 128),
            global_batch=min(shape.global_batch, 8))
    ok, force_swa, reason = resolve_mode(cfg, shape_name)
    rec = {"arch": arch, "shape": shape_name, "multi_pod": multi_pod,
           "tag": tag, "status": "skip", "reason": reason}
    if not ok:
        if verbose:
            print(f"[skip] {arch} x {shape_name}: {reason}")
        return rec

    mesh = (mesh_lib.make_smoke_mesh(multi_pod=multi_pod) if smoke
            else mesh_lib.make_production_mesh(multi_pod=multi_pod))
    nchips = mesh.devices.size
    t0 = monotonic()
    try:
        step, args, specs, out_shardings = build(
            cfg, shape, mesh, tcfg, cache_seq_shard=cache_seq_shard)
        with mesh:
            jitted = (jax.jit(step, out_shardings=out_shardings)
                      if out_shardings is not None else jax.jit(step))
            lowered = jitted.lower(*args)
            t_lower = monotonic() - t0
            compiled = lowered.compile()
            t_compile = monotonic() - t0 - t_lower

        mem = None
        try:
            ma = compiled.memory_analysis()
            if ma is not None:
                mem = {k: getattr(ma, k) for k in
                       ("argument_size_in_bytes", "output_size_in_bytes",
                        "temp_size_in_bytes", "generated_code_size_in_bytes")
                       if hasattr(ma, k)}
        except Exception as e:  # CPU backend may not support it
            mem = {"error": str(e)}

        cost = {}
        try:
            ca = compiled.cost_analysis()
            if isinstance(ca, (list, tuple)):
                ca = ca[0]
            cost = {k: float(v) for k, v in ca.items()
                    if isinstance(v, (int, float)) and
                    k in ("flops", "bytes accessed", "transcendentals",
                          "optimal_seconds")}
        except Exception as e:
            cost = {"error": str(e)}

        hlo = compiled.as_text()
        hc = parse_hlo(hlo)
        crec = profile.record_from_hlo(hc)
        coll = {"total_bytes": crec.collective_bytes,
                "bytes_by_kind": dict(hc.coll_bytes),
                "count_by_kind": dict(hc.coll_count),
                "unknown_trip_counts": crec.unknown_trip_loops}
        # trip-count-expanded per-device totals (see hlo_analysis.py —
        # compiled.cost_analysis() does NOT expand while loops on CPU)
        cost["flops_expanded"] = crec.flops
        cost["bytes_expanded"] = crec.hbm_bytes

        n_params = count_params(cfg)
        n_active = count_params(cfg, active_only=True)
        n_nonembed = count_params(cfg, active_only=True, include_embed=False)
        rec.update(
            status="ok", chips=nchips, force_swa=force_swa,
            seq_len=shape.seq_len, global_batch=shape.global_batch,
            kind=shape.kind, t_lower_s=round(t_lower, 1),
            t_compile_s=round(t_compile, 1),
            params=n_params, active_params=n_active,
            nonembed_active_params=n_nonembed,
            memory=mem, cost=cost, collectives=coll,
            hlo_bytes=len(hlo))
        rec["roofline"] = roofline_terms(rec, tcfg)
        if verbose:
            r = rec["roofline"]
            print(f"[ok] {arch} x {shape_name}{' MP' if multi_pod else ''}"
                  f"{(' ' + tag) if tag else ''}: "
                  f"compute {r['compute_s']:.2e}s  memory {r['memory_s']:.2e}s"
                  f"  collective {r['collective_s']:.2e}s  -> {r['bound']}"
                  f"  (lower {t_lower:.0f}s compile {t_compile:.0f}s)")
    except Exception as e:
        rec.update(status="error", error=f"{type(e).__name__}: {e}",
                   trace=traceback.format_exc()[-2000:])
        if verbose:
            print(f"[ERR] {arch} x {shape_name}: {type(e).__name__}: {e}")

    if save_dir:
        os.makedirs(save_dir, exist_ok=True)
        suffix = ("_mp" if multi_pod else "") + (f"_{tag}" if tag else "")
        path = os.path.join(save_dir,
                            f"{arch.replace('.', '_')}_{shape_name}{suffix}.json")
        slim = {k: v for k, v in rec.items() if k != "trace"}
        with open(path, "w") as f:
            json.dump(slim, f, indent=1, default=str)
    return rec


def roofline_terms(rec: dict, tcfg: TrainConfig) -> dict:
    """The three roofline terms (per brief) from per-device HLO numbers,
    via the one roofline calculator (``repro.obs.profile.roofline``)."""
    chips = rec["chips"]
    crec = profile.record_from_dryrun(rec)
    flops_dev = crec.flops
    peaks = profile.peak_table(mesh_lib.V5E_DEVICE_KIND)
    terms = profile.roofline(crec, peaks, dtype="bf16")
    # MODEL_FLOPS: 6*N_active*D train (D = tokens this step), 2*N*D decode
    toks = rec["global_batch"] * (rec["seq_len"] if rec["kind"] != "decode"
                                  else 1)
    n = rec["nonembed_active_params"]
    if rec["kind"] == "train":
        toks_total = toks * tcfg.local_steps * (1 + tcfg.meta_steps * 0)
        model_flops = 6 * n * toks_total
    elif rec["kind"] == "prefill":
        model_flops = 2 * n * toks
    else:
        model_flops = 2 * n * toks
    hlo_total = flops_dev * chips
    terms.update(model_flops=model_flops, hlo_flops_total=hlo_total,
                 useful_ratio=(model_flops / hlo_total) if hlo_total else 0.0)
    return terms


PAIRS = [(a, s) for a in ARCHS for s in INPUT_SHAPES]


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multipod", action="store_true")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--tag", default="")
    ap.add_argument("--mla-absorbed", action="store_true")
    ap.add_argument("--local-steps", type=int, default=None)
    ap.add_argument("--no-split-fl", action="store_true")
    ap.add_argument("--seq-shard-acts", action="store_true",
                    help="H1: shard hidden states on seq over 'model'")
    ap.add_argument("--cache-seq-shard", action="store_true",
                    help="H2: shard decode KV cache on seq over 'model'")
    ap.add_argument("--fedavg-bf16", action="store_true",
                    help="H3: bf16 delta all-reduce for FedAvg")
    args = ap.parse_args(argv)

    tkw = {}
    if args.local_steps is not None:
        tkw["local_steps"] = args.local_steps
    if args.no_split_fl:
        tkw["split_fl"] = False
    if args.seq_shard_acts:
        tkw["seq_shard_activations"] = True
    if args.fedavg_bf16:
        tkw["fedavg_compress"] = "bf16"
    tcfg = TrainConfig(**tkw)

    pairs = PAIRS if args.all else [(args.arch or "llama3.2-1b",
                                     args.shape or "train_4k")]
    results = []
    for arch, shape in pairs:
        results.append(run_one(arch, shape, multi_pod=args.multipod,
                               smoke=args.smoke, tcfg=tcfg,
                               save_dir=args.out, tag=args.tag,
                               mla_absorbed=args.mla_absorbed,
                               cache_seq_shard=args.cache_seq_shard))
    n_ok = sum(r["status"] == "ok" for r in results)
    n_skip = sum(r["status"] == "skip" for r in results)
    n_err = len(results) - n_ok - n_skip
    print(f"\ndry-run: {n_ok} ok, {n_skip} skip, {n_err} error "
          f"of {len(results)}")
    return 0 if n_err == 0 else 1


if __name__ == "__main__":
    sys.exit(main())
