"""Ablation timing of the wave learner's phases on the real TPU.

Times learner.train_async directly (fixed gradients, sync via a device
fetch) under monkeypatched variants:
  full        — the shipped program
  no-replay   — growth only (replay + emission stubbed)
  no-hist     — hist member scan returns zeros (growth degenerates after
                wave 1, so this times ~1 wave + root; lower bound only)
  W sweep     — wave width sensitivity
"""

import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

import lightgbm_tpu as lgb  # noqa: E402
from lightgbm_tpu.learner_wave import WaveTPUTreeLearner  # noqa: E402
from lightgbm_tpu.observability.attribution import (  # noqa: E402
    force_sync, timeit)


def make(rows=1_000_000, W=None):
    rng = np.random.RandomState(7)
    f = 28
    X = rng.randn(rows, f).astype(np.float64)
    logit = (X[:, 0] * 1.5 + X[:, 1] * X[:, 2] * 0.5 + np.sin(X[:, 3])
             + 0.5 * rng.randn(rows))
    y = (logit > 0).astype(np.float64)
    params = {"objective": "binary", "num_leaves": 255, "max_bin": 255,
              "min_data_in_leaf": 20, "verbosity": -1, "metric": "none"}
    if W is not None:
        params["tpu_wave_width"] = W
    ds = lgb.Dataset(X, label=y, params=params)
    bst = lgb.Booster(params, ds)
    gb = bst.gbdt
    grad, hess = gb.objective.get_gradients(gb.train_score.score)
    n_pad = gb.learner.n_pad
    bag = jnp.ones(n_pad, jnp.float32)
    return gb.learner, grad[0], hess[0], bag


def timed_tree(learner, grad, hess, bag, iters=8):
    # shared timing implementation: best-of, synced on the tree's record
    # output per call
    best = timeit(learner.train_async, grad, hess, bag, iters=iters,
                  warmup=1, sync=lambda out: force_sync(out[0]))
    return best * 1e3


def main():
    variant = sys.argv[1] if len(sys.argv) > 1 else "full"
    rows = int(sys.argv[2]) if len(sys.argv) > 2 else 1_000_000

    label = variant
    if variant.startswith("ablate:"):
        os.environ["LGBMTPU_WAVE_ABLATE"] = variant.split(":", 1)[1]
        variant = "full"
    if variant == "noreplay":
        def fake_replay(self, st, feature_mask):
            M = self.M
            return (st, jnp.zeros(M, bool).at[0].set(True),
                    jnp.zeros(M, jnp.int32), jnp.asarray(0, jnp.int32),
                    jnp.zeros(self.budget, jnp.int32),
                    jnp.zeros(self.budget, jnp.int32),
                    jnp.asarray(0, jnp.int32))

        WaveTPUTreeLearner._replay = fake_replay
    W = None
    if variant.startswith("W"):
        W = int(variant[1:])
    learner, grad, hess, bag = make(rows, W=W)
    assert isinstance(learner, WaveTPUTreeLearner)
    print(f"{label:28s} {timed_tree(learner, grad, hess, bag):8.1f} ms")


if __name__ == "__main__":
    main()
