"""The operation counts the per-layer rates divide by."""

import sys
from pathlib import Path

import pytest

CHIP = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(CHIP))

import harness  # noqa: E402
import job_batch  # noqa: E402

KNN = harness.load_module(CHIP / "metrics" / "knn_gop_per_s.py")
LOOKUP = harness.load_module(CHIP / "metrics" / "lookup_gop_per_s.py")


def test_knn_batch_count_is_three_ops_per_lag_per_pair():
    w = {"op": "knn", "libs": 64, "E": 10, "Lp": 29475}
    assert KNN.knn_ops(w) == 3 * 10 * 29475 ** 2 * 64


def test_multi_e_count_sums_the_levels():
    w = {"op": "knn_multi_e", "series": 2, "E_max": 3, "L": 10, "tau": 1}
    assert KNN.knn_ops(w) == 3 * 2 * (10 ** 2 + 9 ** 2 + 8 ** 2)


def test_lookup_count_is_a_multiply_add_per_neighbour():
    w = {"op": "lookup", "libs": 3, "targets": 5, "k": 11, "rows": 100}
    assert LOOKUP.lookup_ops(w) == 2 * 11 * 100 * 5 * 3
    assert KNN.knn_ops(w) == 0 and LOOKUP.lookup_ops(
        {"op": "knn", "libs": 1, "E": 2, "Lp": 9}) == 0


@pytest.mark.parametrize("steps", [("xmap",), ("optimal_E", "xmap")])
def test_call_work_counts_each_library_once(steps):
    import numpy as np

    config = {"N": 4, "L": 50,
              "edm": {"E_max": 3, "tau": 1, "Tp": 1, "Tp_cross": 0}}
    E_opt = np.array([1, 2, 2, 3])
    work = job_batch.call_work(config, steps, E_opt)
    lookups = [w for w in work if w["op"] == "lookup"]
    cross = [w for w in lookups if w["rows"] == 50 - (w["k"] - 2)]
    assert sorted(w["targets"] for w in cross) == [1, 1, 2]
    if "optimal_E" in steps:
        assert [w["op"] for w in work].count("knn_multi_e") == 1
        assert not any(w["op"] == "knn" for w in work)
        assert len(lookups) == 3 + 3
    else:
        knn = [w for w in work if w["op"] == "knn"]
        assert [(w["E"], w["libs"], w["Lp"]) for w in knn] == [
            (1, 4, 50), (2, 4, 49), (3, 4, 48)]
