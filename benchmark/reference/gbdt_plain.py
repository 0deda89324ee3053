"""Plain reference for gradient-boosted trees as LightGBM defines them.

Straightforward numpy in float64, written from the published definitions:
the text model format, the binary log-loss gradients, the leaf output
-G/(H+l2), the split gain GL^2/(HL+l2) + GR^2/(HR+l2) - GP^2/(HP+l2) and the
rank-sum AUC.  It imports nothing of the program and reads none of its
tables: it is given raw rows, labels and the MODEL TEXT under test, and
judges every tree from the raw values.
"""

from concurrent.futures import ThreadPoolExecutor

import numpy as np

THREADS = 8          # numpy releases the GIL in the gathers below
_CHUNK = 1 << 20


# -- the text model format ----------------------------------------------------

_INT_KEYS = ("split_feature", "left_child", "right_child", "leaf_count",
             "internal_count", "decision_type")
_FLOAT_KEYS = ("threshold", "split_gain", "leaf_value", "internal_value")


def parse_model(text):
    """``{"objective": str, "trees": [tree, ...]}``; a tree is a dict of
    numpy arrays named as in the text (``Tree=`` blocks, ``key=v v v``)."""
    head, *blocks = text.split("\nTree=")
    model = {"objective": "", "trees": []}
    for line in head.splitlines():
        if line.startswith("objective="):
            model["objective"] = line.split("=", 1)[1].strip()
    for block in blocks:
        block = block.split("\nend of trees")[0]
        kv = dict(line.split("=", 1) for line in block.splitlines()[1:]
                  if "=" in line)
        tree = {"num_leaves": int(kv["num_leaves"]),
                "num_cat": int(kv.get("num_cat", 0)),
                "shrinkage": float(kv.get("shrinkage", 1.0))}
        for k in _INT_KEYS:
            tree[k] = np.array(kv.get(k, "").split(), dtype=np.int64)
        for k in _FLOAT_KEYS:
            tree[k] = np.array(kv.get(k, "").split(), dtype=np.float64)
        if tree["num_cat"]:
            raise ValueError("categorical splits are outside this reference")
        model["trees"].append(tree)
    return model


# -- traversal ------------------------------------------------------------------

def _leaf_index_chunk(tree, X):
    n = X.shape[0]
    node = np.zeros(n, np.int64)          # >= 0 internal, < 0 is ~leaf
    if tree["num_leaves"] <= 1:
        return node
    feat, thr = tree["split_feature"], tree["threshold"]
    left, right = tree["left_child"], tree["right_child"]
    active = np.arange(n)
    while active.size:
        nd = node[active]
        x = X[active, feat[nd]]           # numerical split: x <= t goes left
        nxt = np.where(x <= thr[nd], left[nd], right[nd])
        node[active] = nxt
        active = active[nxt >= 0]
    return ~node


def leaf_index(tree, X):
    """The leaf every row of ``X`` (raw values) lands in."""
    n = X.shape[0]
    if n <= _CHUNK:
        return _leaf_index_chunk(tree, X)
    cuts = list(range(0, n, _CHUNK)) + [n]
    with ThreadPoolExecutor(THREADS) as pool:
        parts = pool.map(lambda i: _leaf_index_chunk(tree, X[cuts[i]:cuts[i + 1]]),
                         range(len(cuts) - 1))
        return np.concatenate(list(parts))


def predict_raw(trees, X):
    """Sum of the trees' leaf values, float64."""
    out = np.zeros(X.shape[0], np.float64)
    for tree in trees:
        out += tree["leaf_value"][leaf_index(tree, X)]
    return out


# -- objective --------------------------------------------------------------------

def sigmoid(score):
    return 1.0 / (1.0 + np.exp(-score))


def binary_init_score(y):
    """boost_from_average for binary log loss: the log-odds of the labels."""
    p = float(np.mean(y, dtype=np.float64))
    return float(np.log(p / (1.0 - p)))


def binary_grad_hess(score, y):
    p = sigmoid(score)
    return p - y, p * (1.0 - p)


def round_bf16(a):
    """``a`` rounded to bfloat16 (nearest even), returned as float64."""
    bits = np.asarray(a, np.float32).view(np.uint32).astype(np.uint64)
    bits = (bits + 0x7FFF + ((bits >> 16) & 1)) & 0xFFFF0000
    return bits.astype(np.uint32).view(np.float32).astype(np.float64)


# -- what a tree should hold, from the raw rows ----------------------------------

def _leaf_sums_in(dtype, leaf_idx, values, counts):
    """Per-leaf sums with every value and every partial sum held in
    ``dtype`` (the control's arithmetic), returned as float64."""
    order = np.argsort(leaf_idx, kind="stable")
    starts = np.concatenate([[0], np.cumsum(counts)[:-1]])
    starts = np.minimum(starts, max(leaf_idx.size - 1, 0))
    sums = np.add.reduceat(values[order].astype(dtype), starts).astype(np.float64)
    return np.where(counts > 0, sums, 0.0)


def node_sums(tree, leaf_idx, g, h, dtype=None):
    """Per leaf and per internal node: sum of gradients, of hessians, and
    the row count.  Internal nodes are numbered in the order they were
    split, so a child's index is above its parent's.  ``dtype`` makes the
    leaf sums in that type instead of float64 (the control)."""
    L = tree["num_leaves"]
    counts = np.bincount(leaf_idx, minlength=L)
    if dtype is None:
        leaf = np.stack([np.bincount(leaf_idx, weights=g, minlength=L),
                         np.bincount(leaf_idx, weights=h, minlength=L),
                         counts.astype(np.float64)])
    else:
        leaf = np.stack([_leaf_sums_in(dtype, leaf_idx, g, counts),
                         _leaf_sums_in(dtype, leaf_idx, h, counts),
                         counts.astype(np.float64)])
    inner = np.zeros((3, max(L - 1, 0)))
    for i in range(L - 2, -1, -1):
        for child in (tree["left_child"][i], tree["right_child"][i]):
            inner[:, i] += leaf[:, ~child] if child < 0 else inner[:, child]
    return leaf, inner


def leaf_gain(G, H, l2):
    return G * G / (H + l2)


def split_gains(tree, leaf, inner, l2):
    """The gain of every split the tree holds, from the reference's sums."""
    both = np.concatenate([inner, leaf], axis=1)      # internal, then leaves
    n_inner = inner.shape[1]

    def side(child):
        return both[:, np.where(child < 0, n_inner + ~child, child)]

    lt, rt = side(tree["left_child"]), side(tree["right_child"])
    return (leaf_gain(lt[0], lt[1], l2) + leaf_gain(rt[0], rt[1], l2)
            - leaf_gain(inner[0], inner[1], l2))


def subtree_leaves(tree, node):
    """Boolean mask over leaves: which lie under internal node ``node``."""
    mask = np.zeros(tree["num_leaves"], bool)
    stack = [node]
    while stack:
        i = stack.pop()
        for child in (tree["left_child"][i], tree["right_child"][i]):
            if child < 0:
                mask[~child] = True
            else:
                stack.append(child)
    return mask


# -- the best split a node offers, on the reference's own candidates --------------

def quantile_candidates(X_sample, max_bin):
    """Per feature, up to ``max_bin - 1`` thresholds at equal-frequency
    quantiles of a sample: the reference's own candidate set."""
    q = np.arange(1, max_bin) / max_bin
    return [np.unique(np.quantile(X_sample[:, f].astype(np.float64), q))
            for f in range(X_sample.shape[1])]


def best_gain(Xn, g, h, cands, l2, min_hess, min_data):
    """The largest split gain the rows ``Xn`` offer over every feature and
    every candidate threshold, under the configuration's leaf constraints."""
    G, H = g.sum(), h.sum()
    parent = leaf_gain(G, H, l2)

    def one(f):
        c = cands[f]
        code = np.searchsorted(c, np.ascontiguousarray(Xn[:, f]), side="left")
        m = c.size + 1
        gl = np.cumsum(np.bincount(code, weights=g, minlength=m))[:-1]
        hl = np.cumsum(np.bincount(code, weights=h, minlength=m))[:-1]
        nl = np.cumsum(np.bincount(code, minlength=m))[:-1]
        ok = ((hl >= min_hess) & (H - hl >= min_hess)
              & (nl >= max(min_data, 1)) & (Xn.shape[0] - nl >= max(min_data, 1)))
        if not ok.any():
            return -np.inf
        gl, hl = gl[ok], hl[ok]
        gain = leaf_gain(gl, hl, l2) + leaf_gain(G - gl, H - hl, l2) - parent
        return float(gain.max())

    with ThreadPoolExecutor(THREADS) as pool:
        return max(pool.map(one, range(Xn.shape[1])))


# -- quality ----------------------------------------------------------------------

def auc(y, p):
    """Rank-sum AUC; ties get their average rank."""
    _, inverse, counts = np.unique(p, return_inverse=True, return_counts=True)
    ranks = (np.cumsum(counts) - (counts - 1) / 2.0)[inverse]
    pos = y > 0.5
    n1, n0 = int(pos.sum()), int((~pos).sum())
    return float((ranks[pos].sum() - n1 * (n1 + 1) / 2.0) / (n1 * n0))
