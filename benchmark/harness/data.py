"""The one general data generator.  A generator is a data file under
``benchmark/generators/``; the same seed gives the same rows.

Two keys exist because of what the program compiles into its step (PERF.md,
Open questions): ``fixed_labels`` draws the labels once, from ``label_seed``,
and draws each row from ``--seed`` given its label, by mirroring the rows whose
own label differs; ``feature_shift`` moves every feature by a constant.  Both
leave the learning problem the generator's own.
"""

import numpy as np

from benchmark.harness.paths import BENCH_DIR, load_json

TRAIN_STREAM, HOLDOUT_STREAM, CHECK_STREAM, NODE_STREAM = 0, 1, 2, 3


def rng_for(seed, stream):
    """Independent streams of one seed (any whole number)."""
    return np.random.default_rng([abs(int(seed)), int(stream)])


def _logit(spec, X, noise):
    dtype = X.dtype
    logit = dtype.type(spec["noise_weight"]) * noise
    for term in spec["logit_terms"]:
        kind, w = term["kind"], dtype.type(term["weight"])
        if kind == "linear":
            logit += w * X[:, term["feature"]]
        elif kind == "product":
            a, b = term["features"]
            logit += w * (X[:, a] * X[:, b])
        elif kind == "sin":
            logit += w * np.sin(X[:, term["feature"]])
        else:
            raise ValueError(f"unknown logit term {kind!r}")
    return logit


def _mirror_features(spec):
    """Features whose sign flip negates every term of the logit."""
    flip = []
    for term in spec["logit_terms"]:
        flip.append(term["features"][0] if term["kind"] == "product"
                    else term["feature"])
    for term in spec["logit_terms"]:
        feats = term["features"] if term["kind"] == "product" else [term["feature"]]
        if sum(f in flip for f in feats) != 1 or len(set(flip)) != len(flip):
            raise ValueError("the logit is not odd under one sign flip")
    return flip


def make_rows(generator, seed, stream, rows, features):
    """``rows`` x ``features`` raw values and their 0/1 labels, in bulk."""
    spec = load_json(BENCH_DIR, "generators", generator + ".json")
    if spec["feature_dist"] != "standard_normal":
        raise ValueError(f"unknown feature_dist {spec['feature_dist']!r}")
    dtype = np.dtype(spec["dtype"])
    rng = rng_for(seed, stream)
    X = rng.standard_normal((rows, features), dtype=dtype)
    own = _logit(spec, X, rng.standard_normal(rows, dtype=dtype)) > 0
    fixed = spec.get("fixed_labels")
    if fixed:
        y = rng_for(fixed["label_seed"], stream).random(rows) < 0.5
        other = np.flatnonzero(own != y)
        for f in _mirror_features(spec):
            X[other, f] *= -1
    else:
        y = own
    X += dtype.type(spec.get("feature_shift", 0.0))
    return X, y.astype(np.float32)
