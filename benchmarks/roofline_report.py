"""Render EXPERIMENTS.md §Dry-run / §Roofline tables from the dry-run JSONs.

  PYTHONPATH=src python -m benchmarks.roofline_report [--dir experiments/dryrun]

Roofline terms are RECOMPUTED from each record's stored HLO cost via
``repro.obs.profile`` (the repo's one cost record + roofline calculator)
rather than read back from the JSON, so the table always reflects the
current peak table; ``model_flops``/``useful_ratio`` are taken from the
stored record (they need the arch config the dry-run had in hand).
"""
from __future__ import annotations

import argparse
import glob
import json
import os

from repro.launch.mesh import V5E_DEVICE_KIND
from repro.obs import profile

ARCH_ORDER = ["gemma3-4b", "internvl2-26b", "qwen3-moe-30b-a3b",
              "phi3-medium-14b", "llama3.2-1b", "whisper-medium",
              "qwen2-0.5b", "rwkv6-3b", "jamba-1.5-large-398b",
              "deepseek-v2-236b"]
SHAPE_ORDER = ["train_4k", "prefill_32k", "decode_32k", "long_500k"]

MOVE_HINTS = {
    ("memory", "train"): "bf16 attention probs / remat policy saving dots "
                         "cuts recompute+spill traffic",
    ("memory", "prefill"): "fused flash-attention kernel keeps probs in "
                           "VMEM (kernels/flash_attention.py on TPU)",
    ("memory", "decode"): "larger decode batch per chip or quantized (int8) "
                          "KV cache halves HBM streaming",
    ("collective", "train"): "fewer row-parallel psums: shard activations "
                             "on seq, or all-gather weights once per layer",
    ("collective", "prefill"): "overlap layer all-reduce with next matmul "
                               "(async collectives)",
    ("collective", "decode"): "shard_map seq-parallel flash-decode: psum "
                              "softmax stats, not KV/attention tensors",
    ("compute", "train"): "remat policy: save matmul outputs to avoid "
                          "recompute FLOPs",
    ("compute", "prefill"): "skip padded-vocab logits; fuse SwiGLU matmuls",
    ("compute", "decode"): "absorbed MLA / skip reconstructing per-head KV",
}


def fmt(x, digits=3):
    if x == 0:
        return "0"
    if x >= 0.01:
        return f"{x:.2f}"
    return f"{x:.1e}"


def load(dir_, multipod=False, tag=""):
    recs = {}
    for p in glob.glob(os.path.join(dir_, "*.json")):
        r = json.load(open(p))
        if r.get("multi_pod", False) != multipod:
            continue
        if r.get("tag", "") != tag:
            continue
        recs[(r["arch"], r["shape"])] = r
    return recs


def roofline_table(recs):
    peaks = profile.peak_table(V5E_DEVICE_KIND)
    lines = ["| arch | shape | compute s | memory s | collective s | bound | "
             "MODEL_FLOPS | useful ratio | what moves the bound |",
             "|---|---|---|---|---|---|---|---|---|"]
    for a in ARCH_ORDER:
        for s in SHAPE_ORDER:
            r = recs.get((a, s))
            if r is None:
                continue
            if r["status"] == "skip":
                lines.append(f"| {a} | {s} | — | — | — | SKIP | — | — | "
                             f"{r['reason']} |")
                continue
            if r["status"] != "ok":
                lines.append(f"| {a} | {s} | — | — | — | ERROR | — | — | "
                             f"{r.get('error','')[:60]} |")
                continue
            rf = r["roofline"]
            terms = profile.roofline(profile.record_from_dryrun(r), peaks,
                                     dtype="bf16")
            hint = MOVE_HINTS.get((terms["bound"], r["kind"]), "")
            lines.append(
                f"| {a} | {s} | {fmt(terms['compute_s'])} | "
                f"{fmt(terms['memory_s'])} | {fmt(terms['collective_s'])} | "
                f"**{terms['bound']}** | {rf['model_flops']:.2e} | "
                f"{rf['useful_ratio']:.2f} | {hint} |")
    return "\n".join(lines)


def dryrun_table(recs):
    lines = ["| arch | shape | chips | params/dev MB | temp MB | "
             "flops/dev | bytes/dev | coll bytes/dev | coll ops |",
             "|---|---|---|---|---|---|---|---|---|"]
    for a in ARCH_ORDER:
        for s in SHAPE_ORDER:
            r = recs.get((a, s))
            if r is None or r["status"] != "ok":
                continue
            mem = r.get("memory") or {}
            arg = mem.get("argument_size_in_bytes", 0) / 1e6
            tmp = mem.get("temp_size_in_bytes", 0) / 1e6
            c = r["cost"]
            co = r["collectives"]
            ops = ", ".join(f"{k}:{v}" for k, v in
                            sorted(co["count_by_kind"].items()))
            lines.append(
                f"| {a} | {s} | {r['chips']} | {arg:.0f} | {tmp:.0f} | "
                f"{c.get('flops_expanded', 0):.2e} | "
                f"{c.get('bytes_expanded', 0):.2e} | "
                f"{co['total_bytes']:.2e} | {ops} |")
    return "\n".join(lines)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--dir", default="experiments/dryrun")
    ap.add_argument("--multipod", action="store_true")
    ap.add_argument("--tag", default="")
    args = ap.parse_args(argv)
    recs = load(args.dir, args.multipod, args.tag)
    print(f"## Roofline ({'multi-pod 512' if args.multipod else 'single-pod 256'}"
          f" chips{', tag=' + args.tag if args.tag else ''})\n")
    print(roofline_table(recs))
    print(f"\n## Dry-run detail\n")
    print(dryrun_table(recs))


if __name__ == "__main__":
    main()
