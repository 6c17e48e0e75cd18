"""Seeded data: the same seed gives the same volume, another seed another."""

import numpy as np

import tiny  # noqa: F401
from chipbench.lib import check, data

NV, SV = (8, 12, 12), (256.0, 256.0, 256.0)


def test_same_seed_same_data():
    a = np.asarray(data.phantom(2 ** 31 + 11, NV, SV))
    b = np.asarray(data.phantom(2 ** 31 + 11, NV, SV))
    assert np.array_equal(a, b)


def test_different_seeds_differ_in_values_not_sizes():
    a = np.asarray(data.phantom(3, NV, SV))
    b = np.asarray(data.phantom(4, NV, SV))
    assert a.shape == b.shape == NV and a.dtype == b.dtype == np.float32
    assert not np.array_equal(a, b)
    # the phantom is the same; only the perturbation moves
    assert np.max(np.abs(a - b)) <= data.PERTURBATION


def test_seeds_beyond_32_bits():
    for s in (0, 2 ** 31 - 1, 2 ** 31 + 5, 2 ** 40 + 3):
        assert np.isfinite(np.asarray(data.phantom(s, NV, SV))).all()
    assert not np.array_equal(np.asarray(data.phantom(2 ** 32 + 1, NV, SV)),
                              np.asarray(data.phantom(1, NV, SV)))


def test_check_samples_follow_the_seed():
    angles = np.linspace(0, 2 * np.pi, 512, endpoint=False).astype(np.float32)
    a = check.sample_angles(7, angles, 4, n_shards=4)
    assert np.array_equal(a, check.sample_angles(7, angles, 4, n_shards=4))
    assert len(a) == 8 and len(set(a.tolist())) == 8
    assert check.sample_box(7, (512,) * 3, 8) == check.sample_box(7, (512,) * 3, 8)
    assert not np.array_equal(a, check.sample_angles(8, angles, 4, n_shards=4))
