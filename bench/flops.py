"""Operations and bytes that each pass and each kernel needs, from shapes.

Counts are of multiply-adds as two operations, in the convolutions, the
matrix products and the linear head (what the MXU does); normalisation,
activations and the optimiser's elementwise work are left out. A training
pass is three forward passes (forward, gradient of the input, gradient of
the weights), as is usual; recomputation is not counted.
"""
from __future__ import annotations

from typing import Dict, Iterator, Tuple


def _convs(depth: int, widen: int, image_size: int, channels: int
           ) -> Iterator[Tuple[str, int, int, int, int, int]]:
    """(part, group, spatial size out, kernel, cin, cout) per convolution,
    for a WRN-depth-widen; ``part`` is the group index 0 (stem) to 3."""
    n = (depth - 4) // 6
    w = (16, 16 * widen, 32 * widen, 64 * widen)
    s = image_size
    yield (0, s, 3, channels, w[0], 1)
    for g in range(1, 4):
        if g > 1:
            s //= 2
        for b in range(n):
            cin = w[g - 1] if b == 0 else w[g]
            yield (g, s, 3, cin, w[g], 1)
            yield (g, s, 3, w[g], w[g], 1)
            if cin != w[g]:
                yield (g, s, 1, cin, w[g], 1)


def forward_flops(model: Dict, parts=(0, 1, 2, 3), head: bool = True
                  ) -> int:
    """Forward operations per image through the given parts (0 = stem,
    1-3 = groups) and, with ``head``, the linear head."""
    total = 0
    for g, s, k, cin, cout, _ in _convs(model["depth"], model["widen"],
                                        model["image_size"],
                                        model["channels"]):
        if g in parts:
            total += 2 * s * s * k * k * cin * cout
    if head:
        total += 2 * 64 * model["widen"] * model["num_classes"]
    return total


def lower_parts(model: Dict):
    return tuple(range(0, model["split_group"] + 1))


def upper_parts(model: Dict):
    return tuple(range(model["split_group"] + 1, 4))


def param_count(model: Dict) -> int:
    """Weights of every convolution, two per channel of every batch norm,
    and the head's weights and bias."""
    total = 0
    n = (model["depth"] - 4) // 6
    w = (16, 16 * model["widen"], 32 * model["widen"], 64 * model["widen"])
    for _, _, k, cin, cout, _ in _convs(model["depth"], model["widen"],
                                        model["image_size"],
                                        model["channels"]):
        total += k * k * cin * cout
    for g in range(1, 4):
        for b in range(n):
            cin = w[g - 1] if b == 0 else w[g]
            total += 2 * cin + 2 * w[g]
    total += 2 * w[3]
    total += w[3] * model["num_classes"] + model["num_classes"]
    return total


def split_features(model: Dict) -> int:
    """Features of one split-layer map (channels x height x width)."""
    w = (16, 16 * model["widen"], 32 * model["widen"], 64 * model["widen"])
    s = model["image_size"] // (2 ** (model["split_group"] - 1))
    return w[model["split_group"]] * s * s


def round_model_flops(model: Dict, fl: Dict, samples_per_client: int
                      ) -> Dict[str, int]:
    """Model operations of one round of ``clients_per_round`` clients:
    LocalUpdate's training passes, Extract's lower forward and
    MetaTraining's training passes on the upper part."""
    c = int(fl["clients_per_round"])
    n = samples_per_client
    bs = min(int(fl["local_batch_size"]), n)
    local_images = (n // bs) * bs * int(fl.get("local_epochs", 1))
    full = forward_flops(model)
    rows = c * int(fl["clusters_per_class"]) * int(fl["classes_per_client"])
    mbs = min(int(fl["meta_batch_size"]), rows)
    meta_images = max(rows // mbs, 1) * mbs * int(fl["meta_epochs"])
    return {
        "local_update": 3 * full * local_images * c,
        "extract": forward_flops(model, lower_parts(model), head=False) * n * c,
        "meta_train": 3 * forward_flops(model, upper_parts(model))
        * meta_images,
    }


def lloyd_step(n: int, p: int, k: int) -> Tuple[int, int]:
    """(operations, bytes) of one fused Lloyd sweep over n rows of p
    features and k cluster slots: the distance products and the centroid
    sums (2nkp each); reading the rows, the slot mask and the centroids,
    writing each row's assignment and distance and the slots' sums and
    counts, in 4-byte words."""
    flops = 4 * n * k * p
    nbytes = 4 * (n * p + n * k + k * p + 2 * n + k * p + k)
    return flops, nbytes


def quantize(n: int, d: int) -> Tuple[int, int]:
    """(operations, bytes) of the affine int8 quantize of an (n, d) float32
    tensor: a pass for the minimum and maximum, a pass that writes one byte
    per element. Bound by memory: its operations are a few per element."""
    return 4 * n * d, 4 * n * d * 2 + n * d + 4 * n
