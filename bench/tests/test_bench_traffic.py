"""The benchmark's copy of the data generator and partitioner draws what
the program's own do, element for element."""
import numpy as np
import pytest

import tiny  # noqa: F401  (puts src/ and bench/ on the path)
from repro.data import SyntheticImageDataset, partition_k_shards
from traffic.synthetic import k_shards, synthetic_images


@pytest.mark.parametrize("n,size,seed", [(300, 32, 5), (257, 16, 2**31 + 77)])
def test_synthetic_images_match_the_program(n, size, seed):
    x, y = synthetic_images(n, image_size=size, seed=seed)
    ds = SyntheticImageDataset(n, image_size=size, seed=seed)
    assert x.dtype == ds.x.dtype and x.shape == ds.x.shape
    assert np.array_equal(x, ds.x)
    assert np.array_equal(y, ds.y)


@pytest.mark.parametrize("clients,k,per", [(4, 2, 50), (7, 3, 90)])
def test_k_shards_match_partition_k_shards(clients, k, per):
    x, y = synthetic_images(400, image_size=16, seed=3)
    ds = SyntheticImageDataset(400, image_size=16, seed=3)
    ours = k_shards(y, 10, clients, k, per, seed=9)
    theirs = partition_k_shards(ds, clients, k_classes=k,
                                samples_per_client=per, seed=9)
    for (idx, classes), c in zip(ours, theirs):
        assert np.array_equal(x[idx], c.data.x)
        assert np.array_equal(classes, c.classes)
