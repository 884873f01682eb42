"""Operation and byte counts of one iteration come from the algorithm at
the cell's shapes, not from the path that implements it."""
from __future__ import annotations

import json

import pytest

from bench import work
from bench.harness import BENCH_DIR


@pytest.mark.parametrize("config,traffic", [("fleet16k-svm", "sim-run-t20"),
                                            ("paper-fmnist-lenet", "serve-grid16")])
def test_dense_ell_and_pallas_paths_count_alike(config, traffic):
    c = json.loads((BENCH_DIR / "configs" / f"{config}.json").read_text())
    t = json.loads((BENCH_DIR / "traffic" / f"{traffic}.json").read_text())
    counts = [work.iteration_work(dict(c, mixing=mixing), t, links=1234.0)
              for mixing in ("dense", "ell", "pallas", "sparse_pallas")]
    assert all(cnt == counts[0] for cnt in counts)
    assert counts[0]["flops"] > 0 and counts[0]["bytes"] > 0


@pytest.mark.parametrize("model", ["svm", "cnn"])
def test_parameter_count_matches_the_model(model):
    from repro.fl.modelspec import make_model_spec

    assert (work.model_shapes(model, 784, 10)["D"]
            == make_model_spec(model, dim=784, n_classes=10).flat_dim)


def test_svm_fleet_iteration_by_hand():
    m, D, b, dim, C, n_test, links = 4, 7850, 16, 784, 10, 400, 6
    got = work.iteration_work(
        {"model": "svm", "dim": dim, "n_classes": C, "m": m, "batch": b,
         "n_test": n_test}, {"T": 20, "eval_every": 10}, links=links)
    evals = 3 / 20  # iterations 0, 10 and the last
    flops = (3 * m * D + 2 * D * (m + links) + m * b * 2 * (2 * dim * C)
             + 2 * m * D + 3 * m * D + evals * m * n_test * 2 * dim * C)
    words = 4 * m * D + m * b * (dim + 1) + evals * (m * D + n_test * (dim + 1))
    assert got["flops"] == pytest.approx(flops)
    assert got["bytes"] == pytest.approx(4 * words)


def test_unknown_model_is_an_error():
    with pytest.raises(ValueError):
        work.model_shapes("transformer", 784, 10)
