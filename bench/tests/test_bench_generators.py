"""The benchmark's generators: sizes, determinism per seed, and the
Graph500 rules (permuted labels, weights in [0, 1))."""
import numpy as np
import pytest

from bench import run

SEED = 2**33 + 7  # larger than 32 bits hold


def _generate(config, seed=SEED, **params):
    doc = run.load_json("configs", config)
    gen = run.load_module("generators", doc["generator"])
    return gen.generate({**doc["params"], **params}, seed)


def test_graph500_sizes_and_weights():
    e = _generate("graph500-s18", scale=10)
    assert e["n"] == 1024
    assert e["src"].shape == e["dst"].shape == e["weight"].shape == (16384,)
    for ids in (e["src"], e["dst"]):
        assert ids.min() >= 0 and ids.max() < 1024
    assert e["weight"].dtype == np.float32
    assert e["weight"].min() >= 0.0 and e["weight"].max() < 1.0


def test_graph500_labels_are_permuted():
    # unpermuted, R-MAT's hubs sit at the lowest ids
    e = _generate("graph500-s18", scale=12)
    deg = np.bincount(np.concatenate([e["src"], e["dst"]]), minlength=e["n"])
    hubs = np.argsort(deg)[-40:]
    assert 0.25 * e["n"] < hubs.mean() < 0.75 * e["n"]
    assert deg.max() > 20 * deg.mean()  # still skewed


@pytest.mark.parametrize("config,params", [
    ("graph500-s18", {"scale": 9}),
    ("amz-table2", {"n": 2048, "n_edges": 12000})])
def test_generators_are_deterministic_per_seed(config, params):
    a, b = _generate(config, **params), _generate(config, **params)
    c = _generate(config, seed=SEED + 1, **params)
    for k in ("src", "dst", "weight"):
        np.testing.assert_array_equal(a[k], b[k])
    assert not np.array_equal(a["dst"], c["dst"])


def test_stand_in_keeps_its_degree_sequence_across_seeds():
    a = _generate("amz-table2", n=2048, n_edges=12000)
    c = _generate("amz-table2", seed=SEED + 1, n=2048, n_edges=12000)
    np.testing.assert_array_equal(a["src"], c["src"])
    # crawl order: out-degree falls with the id
    deg = np.bincount(a["src"], minlength=a["n"])
    assert np.all(np.diff(deg) <= 0)


@pytest.mark.parametrize("n,n_edges,max_degree", [
    (2048, 12000, 2770), (410236, 3356824, 2770), (1000, 1000, 5),
    (1000, 5000, 5)])
def test_stand_in_realises_its_edge_count(n, n_edges, max_degree):
    gen = run.load_module("generators", "powerlaw_stand_in")
    deg = gen.degrees(n, n_edges, 1.2, max_degree)
    assert deg.sum() == n_edges and deg.shape == (n,)
    assert deg.min() >= 1 and deg.max() <= max_degree
    assert np.all(np.diff(deg) <= 0)


def test_stand_in_refuses_an_edge_count_it_cannot_hold():
    gen = run.load_module("generators", "powerlaw_stand_in")
    with pytest.raises(ValueError):
        gen.degrees(1000, 5001, 1.2, 5)
