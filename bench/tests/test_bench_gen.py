"""The device generator at small scale: determinism, sizes, ranges,
symmetry and the Graph500 quadrant frequencies."""
import jax
import numpy as np
import pytest

from bench import gen

KRON = dict(name="kron10", generator="kron", scale=10, num_nodes=1024, num_edges=1 << 14,
            num_arcs=1 << 15, a=0.57, b=0.19, c=0.19)
BIG_SEED = 2**31 + 977


def edges(config, seed):
    coo = gen.generate(config, gen.seed_key(seed))
    return np.asarray(coo.src), np.asarray(coo.dst), coo


def test_same_seed_same_graph_other_seed_other_graph():
    config = KRON
    s1, d1, coo = edges(config, BIG_SEED)
    s2, d2, _ = edges(config, BIG_SEED)
    s3, d3, _ = edges(config, BIG_SEED + 1)
    s4, _, _ = edges(config, BIG_SEED + (1 << 32))  # the high word counts
    assert np.array_equal(s1, s2) and np.array_equal(d1, d2)
    assert not np.array_equal(s1, s3) and not np.array_equal(s1, s4)
    assert coo.src.dtype == np.int32 and coo.dst.dtype == np.int32
    assert coo.num_edges == config["num_arcs"] and coo.num_nodes == config["num_nodes"]
    for x in (s1, d1):
        assert x.min() >= 0 and x.max() < config["num_nodes"]


def test_kron_quadrant_frequencies_within_sampling_error():
    a, b, c = KRON["a"], KRON["b"], KRON["c"]
    m = 1 << 16
    src, dst = jax.device_get(gen.kron_bits(gen.seed_key(BIG_SEED), 8, m, gen.quadrant_thresholds(a, b, c)))
    want = np.array([a, b, c, 1 - a - b - c])  # (src bit, dst bit) = 00, 01, 10, 11
    sd = np.sqrt(want * (1 - want) / m)
    for bit in range(8):
        q = 2 * ((src >> bit) & 1) + ((dst >> bit) & 1)
        freq = np.bincount(q, minlength=4) / m
        assert np.all(np.abs(freq - want) < 5 * sd), (bit, freq)


def test_kron_is_skewed():
    src, _, _ = edges(dict(KRON, scale=12, num_nodes=4096, num_edges=1 << 16, num_arcs=1 << 17), BIG_SEED)
    # Graph500's hubs: the top 1% of vertices hold far more than 1% of arcs
    assert np.sort(np.bincount(src, minlength=4096))[-41:].sum() / src.size > 0.1


def test_arcs_are_both_directions_of_every_tuple():
    src, dst, _ = edges(KRON, BIG_SEED)
    m = KRON["num_edges"]
    assert np.array_equal(src[:m], dst[m:]) and np.array_equal(dst[:m], src[m:])
    assert np.any(src[:m] == dst[:m])  # self loops are kept, as Graph500 emits them
    assert np.unique(src[:m].astype(np.int64) << 32 | dst[:m]).size < m  # and repeated tuples


@pytest.mark.parametrize("bad", [dict(generator="road"), dict(num_nodes=1000), dict(num_arcs=1 << 14)],
                         ids=["generator", "scale", "arcs"])
def test_unknown_generator_and_bad_sizes_raise(bad):
    with pytest.raises(ValueError):
        gen.generate(dict(KRON, **bad), gen.seed_key(1))
