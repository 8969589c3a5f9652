"""The benchmark's own copy of the synthetic CIFAR-10 stand-in and the
paper's k-class shard partition.

``synthetic_images`` draws exactly what ``repro.data.SyntheticImageDataset``
draws, element for element, for the same arguments: class/mode prototypes
upsampled from a coarse grid, one random circular shift per image, pixel
noise, then a global normalisation. The per-image ``np.roll`` loop of the
original is one gather here. ``k_shards`` is ``partition_k_shards`` with the
same random stream. Both return plain arrays; ``bench.build`` wraps them in
the program's ``Dataset``/``ClientData`` types.
"""
from __future__ import annotations

from typing import List, Tuple

import numpy as np


def synthetic_images(num_samples: int, image_size: int = 32,
                     channels: int = 3, num_classes: int = 10,
                     modes_per_class: int = 4, noise: float = 0.35,
                     seed: int = 0, structure_seed: int = 0
                     ) -> Tuple[np.ndarray, np.ndarray]:
    """(x (N, H, W, C) float32, y (N,) int32) from ``seed``."""
    srng = np.random.default_rng(structure_seed)
    rng = np.random.default_rng(seed)
    coarse = max(4, image_size // 4)
    protos = srng.normal(0, 1.0, (num_classes, modes_per_class, coarse,
                                  coarse, channels))
    rep = image_size // coarse
    protos = protos.repeat(rep, axis=2).repeat(rep, axis=3)
    protos = protos.astype(np.float32)
    y = rng.integers(0, num_classes, num_samples).astype(np.int32)
    modes = rng.integers(0, modes_per_class, num_samples)
    shifts = rng.integers(-2, 3, (num_samples, 2))
    # np.roll by (sy, sx): out[h, w] = in[(h - sy) % H, (w - sx) % W]
    h = protos.shape[2]
    w = protos.shape[3]
    rows = (np.arange(h)[None, :] - shifts[:, :1]) % h        # (N, H)
    cols = (np.arange(w)[None, :] - shifts[:, 1:]) % w        # (N, W)
    proto_id = (y.astype(np.int64) * modes_per_class + modes)[:, None, None]
    flat = protos.reshape((-1,) + protos.shape[2:])
    x = flat[proto_id, rows[:, :, None], cols[:, None, :]]
    x += rng.normal(0, noise, x.shape).astype(np.float32)
    x = (x - x.mean()) / (x.std() + 1e-6)
    return x.astype(np.float32, copy=False), y


def k_shards(y: np.ndarray, num_classes: int, num_clients: int,
             k_classes: int, samples_per_client: int, seed: int
             ) -> List[Tuple[np.ndarray, np.ndarray]]:
    """Per client: (indices into the dataset, sorted classes held). Each
    client draws ``k_classes`` classes and ``samples_per_client //
    k_classes`` samples of each, recycling indices at random when a class
    runs dry (the paper's §4.1 layout for 2 classes, 2,500 images)."""
    rng = np.random.default_rng(seed)
    by_class = {c: list(rng.permutation(np.where(y == c)[0]))
                for c in range(num_classes)}
    present = np.unique(y)
    out = []
    for _ in range(num_clients):
        classes = rng.choice(present, size=min(k_classes, len(present)),
                             replace=False)
        per_class = samples_per_client // k_classes
        idx = []
        for c in classes:
            pool = by_class[int(c)]
            take = pool[:per_class]
            if len(take) < per_class:
                src = np.where(y == c)[0]
                extra = rng.choice(src, per_class - len(take), replace=True)
                take = take + list(extra)
            by_class[int(c)] = pool[per_class:]
            idx.extend(take)
        out.append((np.asarray(idx, np.int64), np.sort(classes)))
    return out
