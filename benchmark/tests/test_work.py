"""The histogram work function on a hand-built three-leaf tree."""

import numpy as np

from benchmark.harness import work
from benchmark.reference import gbdt_plain

THREE_LEAVES = """tree
version=v2
objective=binary

Tree=0
num_leaves=3
num_cat=0
split_feature=0 1
split_gain=10 5
threshold=0.5 0.25
decision_type=2 2
left_child=1 -1
right_child=-2 -3
leaf_value=0.1 -0.2 0.3
leaf_count=100 700 200
internal_value=0 0.1
internal_count=1000 300
shrinkage=1

end of trees
"""


def test_hist_rows_three_leaves():
    tree = gbdt_plain.parse_model(THREE_LEAVES)["trees"][0]
    # root: 1000 rows.  Split 0: children 300 and 700, the smaller is 300.
    # Split 1: children 100 and 200, the smaller is 100.
    assert work.hist_rows(tree) == 1000 + 300 + 100
    # 28 one-byte bin codes and 8 bytes of gradient and hessian a row
    assert work.hist_bytes(tree, 28) == 1400 * 36


def test_single_leaf_tree_needs_nothing():
    tree = {"num_leaves": 1, "internal_count": np.array([], np.int64),
            "leaf_count": np.array([5]), "left_child": np.array([], np.int64),
            "right_child": np.array([], np.int64)}
    assert work.hist_rows(tree) == 0


def test_least_seconds_names_the_binding_peak():
    peaks = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert work.least_seconds(flops=100.0, nbytes=50.0, peaks=peaks) == 5.0
    assert work.least_seconds(flops=1000.0, nbytes=50.0, peaks=peaks) == 10.0


def test_unknown_device_is_an_error():
    import pytest
    from benchmark.harness import device
    assert device.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        device.peaks("cpu")


def test_traversal_and_sums_on_the_three_leaf_tree():
    tree = gbdt_plain.parse_model(THREE_LEAVES)["trees"][0]
    X = np.array([[0.4, 0.2], [0.4, 0.3], [0.6, 0.0], [0.5, 0.25]])
    li = gbdt_plain.leaf_index(tree, X)
    assert li.tolist() == [0, 2, 1, 0]
    g, h = np.array([1.0, 2.0, 3.0, 4.0]), np.ones(4)
    leaf, inner = gbdt_plain.node_sums(tree, li, g, h)
    assert leaf[0].tolist() == [5.0, 3.0, 2.0]
    assert inner[0].tolist() == [10.0, 7.0] and inner[2].tolist() == [4.0, 3.0]
    gains = gbdt_plain.split_gains(tree, leaf, inner, 0.0)
    assert np.isclose(gains[0], 49 / 3 + 9 / 1 - 100 / 4)
