"""The seeded generator of weights and inputs: the same seed gives the
same arrays, another seed other arrays, and a seed past 32 bits works."""
import numpy as np
import pytest

from bench.lib import weights

LAYERS = [{"name": "a", "C": 3, "Cout": 4, "k": 3},
          {"name": "b", "C": 4, "Cout": 2, "k": 5}]


def _all(seed):
    kernels, biases = weights.params(LAYERS, seed)
    (x,) = weights.normal_arrays([(2, 3, 8, 8)], seed)
    return [np.asarray(a) for a in (*kernels.values(), *biases.values(), x)]


@pytest.mark.parametrize("seed", [0, 2**31 + 17])
def test_same_seed_same_arrays(seed):
    for a, b in zip(_all(seed), _all(seed)):
        assert np.array_equal(a, b)


def test_every_array_changes_with_the_seed():
    for a, b in zip(_all(1), _all(2)):
        assert a.shape == b.shape and not np.allclose(a, b)
