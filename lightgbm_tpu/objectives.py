"""Objective functions — gradients/hessians as pure JAX.

TPU-native re-design of `src/objective/` (interface
`include/LightGBM/objective_function.h:31-74`; factory
`src/objective/objective_function.cpp:10-82`).  Each objective is a jitted
element-wise map ``score -> (grad, hess)`` over the padded row axis; padded
rows are neutralized downstream by the bagging/validity mask, so objectives
never see them.

Formulas are ported 1:1 from the reference (citations on each class);
multiclass keeps the reference's (K, N) score layout — K trees per iteration.
``RenewTreeOutput`` (percentile leaf refinement for L1-family objectives,
`regression_objective.hpp:224-298`) is implemented host-side in
``renew_tree_output``.
"""

from __future__ import annotations

import contextlib
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .binning import kEpsilon
from .config import Config
from .dataset import Metadata


def _sigmoid(x):
    return 1.0 / (1.0 + jnp.exp(-x))


class ObjectiveFunction:
    """Base (reference `objective_function.h:15-74`)."""

    name = "none"
    is_constant_hessian = False
    num_model_per_iteration = 1
    need_group = False
    #: attributes that may hold row-aligned device arrays: what
    #: `parallel/learners.py` shards over the mesh
    ROW_ARRAYS = ("label", "weights", "trans_label", "label_sign",
                  "label_w", "label_weight", "label_onehot")
    #: further device arrays the gradients read that are not row-aligned
    #: (an objective's own tables): never sharded by rows
    TABLE_ARRAYS: Tuple[str, ...] = ()

    def __init__(self, cfg: Config):
        self.cfg = cfg
        self.num_data = 0
        self.label: Optional[jnp.ndarray] = None
        self.weights: Optional[jnp.ndarray] = None

    def init(self, metadata: Metadata, num_data: int, num_data_padded: int) -> None:
        self.num_data = num_data
        lab = np.zeros(num_data_padded, dtype=np.float32)
        lab[:num_data] = metadata.label
        self.label = jnp.asarray(lab)
        if metadata.weights is not None:
            w = np.zeros(num_data_padded, dtype=np.float32)
            w[:num_data] = metadata.weights
            self.weights = jnp.asarray(w)
        self._np_label = metadata.label
        self._np_weights = metadata.weights
        self.metadata = metadata

    def device_arrays(self) -> dict:
        """Every device array the gradients read, by attribute name: what a
        jitted caller passes as ARGUMENTS (``bind``), so that none is
        compiled into its program as a constant."""
        return {n: getattr(self, n) for n in self.ROW_ARRAYS
                + self.TABLE_ARRAYS
                if hasattr(getattr(self, n, None), "shape")}

    @contextlib.contextmanager
    def bind(self, arrs: dict):
        """The objective with ``arrs`` (traced arguments) in place of its
        own arrays, for the duration of a trace."""
        saved = {n: getattr(self, n) for n in arrs}
        for n, v in arrs.items():
            setattr(self, n, v)
        try:
            yield self
        finally:
            for n, v in saved.items():
                setattr(self, n, v)

    # grad/hess for one class-tree; score shape (N_pad,)
    def get_gradients(self, score: jnp.ndarray, class_id: int = 0
                      ) -> Tuple[jnp.ndarray, jnp.ndarray]:
        raise NotImplementedError

    def boost_from_score(self, class_id: int = 0) -> float:
        return 0.0

    def convert_output(self, raw: np.ndarray) -> np.ndarray:
        return raw

    def class_need_train(self, class_id: int) -> bool:
        return True

    def _w(self, g, h):
        if self.weights is None:
            return g, h
        return g * self.weights, h * self.weights

    def renew_tree_output(self, tree, score: np.ndarray, leaf_id: np.ndarray,
                          mask: np.ndarray) -> None:
        """Leaf refinement hook (`objective_function.h:58-66`); default no-op."""

    @property
    def needs_renew_tree_output(self) -> bool:
        """True when this objective overrides ``renew_tree_output`` — the
        boosting loop then pulls scores to host per iteration; objectives
        that don't renew skip that sync entirely."""
        return type(self).renew_tree_output is not \
            ObjectiveFunction.renew_tree_output

    def to_string(self) -> str:
        return self.name


# --------------------------- regression family ----------------------------

class RegressionL2(ObjectiveFunction):
    """`regression_objective.hpp:71-180` (sqrt transform at `:77-101`)."""
    name = "regression"
    is_constant_hessian = True  # without weights

    def __init__(self, cfg):
        super().__init__(cfg)
        self.sqrt = cfg.reg_sqrt

    def init(self, metadata, num_data, num_data_padded):
        super().init(metadata, num_data, num_data_padded)
        if self.sqrt:
            lab = np.asarray(self.label)
            self.trans_label = jnp.asarray(np.sign(lab) * np.sqrt(np.abs(lab)))
        else:
            self.trans_label = self.label
        self.is_constant_hessian = self.weights is None

    def get_gradients(self, score, class_id=0):
        g = score - self.trans_label
        h = jnp.ones_like(score)
        return self._w(g, h)

    def boost_from_score(self, class_id=0):
        lab = np.asarray(self.trans_label)[:self.num_data].astype(np.float64)
        if self._np_weights is not None:
            w = self._np_weights.astype(np.float64)
            return float((lab * w).sum() / w.sum())
        return float(lab.mean())

    def convert_output(self, raw):
        if self.sqrt:
            return np.sign(raw) * raw * raw
        return raw


class RegressionL1(RegressionL2):
    """`regression_objective.hpp:182-298`; leaf renewed to weighted median."""
    name = "regression_l1"
    is_constant_hessian = True

    def get_gradients(self, score, class_id=0):
        diff = score - self.trans_label
        g = jnp.sign(diff)
        h = jnp.ones_like(score)
        return self._w(g, h)

    def boost_from_score(self, class_id=0):
        lab = self._np_label.astype(np.float64)
        if self._np_weights is not None:
            return _weighted_percentile(lab, self._np_weights, 0.5)
        return float(np.percentile(lab, 50, method="lower")
                     if len(lab) % 2 else np.median(lab))

    def renew_tree_output(self, tree, score, leaf_id, mask):
        _percentile_renew(tree, self._np_label, self._np_weights, score,
                          leaf_id, mask, 0.5)


class RegressionHuber(RegressionL2):
    """`regression_objective.hpp:300-360`."""
    name = "huber"
    is_constant_hessian = False

    def get_gradients(self, score, class_id=0):
        a = self.cfg.alpha
        diff = score - self.trans_label
        g = jnp.where(jnp.abs(diff) <= a, diff, jnp.sign(diff) * a)
        h = jnp.ones_like(score)
        return self._w(g, h)


class RegressionFair(RegressionL2):
    """`regression_objective.hpp:362-407`."""
    name = "fair"
    is_constant_hessian = False

    def get_gradients(self, score, class_id=0):
        c = self.cfg.fair_c
        x = score - self.trans_label
        g = c * x / (jnp.abs(x) + c)
        h = c * c / (jnp.abs(x) + c) ** 2
        return self._w(g, h)

    def boost_from_score(self, class_id=0):
        return 0.0


class RegressionPoisson(RegressionL2):
    """`regression_objective.hpp:409-487`; score is log(E[y])."""
    name = "poisson"
    is_constant_hessian = False

    def get_gradients(self, score, class_id=0):
        g = jnp.exp(score) - self.label
        h = jnp.exp(score + self.cfg.poisson_max_delta_step)
        return self._w(g, h)

    def boost_from_score(self, class_id=0):
        mean = RegressionL2.boost_from_score(self, class_id)
        return math.log(max(mean, 1e-20))

    def convert_output(self, raw):
        return np.exp(raw)


class RegressionQuantile(RegressionL2):
    """`regression_objective.hpp:489-616`."""
    name = "quantile"
    is_constant_hessian = True

    def get_gradients(self, score, class_id=0):
        a = self.cfg.alpha
        g = jnp.where(score > self.label, 1.0 - a, -a)
        h = jnp.ones_like(score)
        return self._w(g, h)

    def boost_from_score(self, class_id=0):
        lab = self._np_label.astype(np.float64)
        if self._np_weights is not None:
            return _weighted_percentile(lab, self._np_weights, self.cfg.alpha)
        return _percentile(lab, self.cfg.alpha)

    def renew_tree_output(self, tree, score, leaf_id, mask):
        _percentile_renew(tree, self._np_label, self._np_weights, score,
                          leaf_id, mask, self.cfg.alpha)


class RegressionMAPE(RegressionL2):
    """`regression_objective.hpp:618-735`."""
    name = "mape"
    is_constant_hessian = False

    def init(self, metadata, num_data, num_data_padded):
        super().init(metadata, num_data, num_data_padded)
        lw = 1.0 / np.maximum(1.0, np.abs(np.asarray(self.label)))
        if self.weights is not None:
            lw = lw * np.asarray(self.weights)
        self.label_weight = jnp.asarray(lw.astype(np.float32))

    def get_gradients(self, score, class_id=0):
        diff = score - self.label
        g = jnp.sign(diff) * self.label_weight
        h = jnp.ones_like(score) if self.weights is None else self.weights
        return g, h

    def boost_from_score(self, class_id=0):
        lw = 1.0 / np.maximum(1.0, np.abs(self._np_label.astype(np.float64)))
        if self._np_weights is not None:
            lw = lw * self._np_weights
        return _weighted_percentile(self._np_label.astype(np.float64), lw, 0.5)

    def renew_tree_output(self, tree, score, leaf_id, mask):
        lw = 1.0 / np.maximum(1.0, np.abs(self._np_label.astype(np.float64)))
        if self._np_weights is not None:
            lw = lw * self._np_weights
        _percentile_renew(tree, self._np_label, lw, score, leaf_id, mask, 0.5)


class RegressionGamma(RegressionPoisson):
    """`regression_objective.hpp:737-768`."""
    name = "gamma"

    def get_gradients(self, score, class_id=0):
        g = 1.0 - self.label / jnp.exp(score)
        h = self.label / jnp.exp(score)
        return self._w(g, h)


class RegressionTweedie(RegressionPoisson):
    """`regression_objective.hpp:770-805`."""
    name = "tweedie"

    def get_gradients(self, score, class_id=0):
        rho = self.cfg.tweedie_variance_power
        e1 = jnp.exp((1.0 - rho) * score)
        e2 = jnp.exp((2.0 - rho) * score)
        g = -self.label * e1 + e2
        h = -self.label * (1.0 - rho) * e1 + (2.0 - rho) * e2
        return self._w(g, h)


# ------------------------------- binary -----------------------------------

class BinaryLogloss(ObjectiveFunction):
    """`src/objective/binary_objective.hpp:13-170`."""
    name = "binary"

    def init(self, metadata, num_data, num_data_padded):
        super().init(metadata, num_data, num_data_padded)
        lab = self._np_label
        cnt_pos = int((lab > 0).sum())
        cnt_neg = int(len(lab) - cnt_pos)
        self.need_train = not (cnt_pos == 0 or cnt_neg == 0)
        lw_neg, lw_pos = 1.0, 1.0
        if self.cfg.is_unbalance and cnt_pos > 0 and cnt_neg > 0:
            if cnt_pos > cnt_neg:
                lw_neg = cnt_pos / cnt_neg
            else:
                lw_pos = cnt_neg / cnt_pos
        lw_pos *= self.cfg.scale_pos_weight
        self.label_weights = (lw_neg, lw_pos)
        self.label_sign = jnp.where(self.label > 0, 1.0, -1.0)
        self.label_w = jnp.where(self.label > 0, lw_pos, lw_neg)
        self._cnt_pos, self._cnt_neg = cnt_pos, cnt_neg

    def get_gradients(self, score, class_id=0):
        sig = self.cfg.sigmoid
        response = -self.label_sign * sig / (
            1.0 + jnp.exp(self.label_sign * sig * score))
        abs_r = jnp.abs(response)
        g = response * self.label_w
        h = abs_r * (sig - abs_r) * self.label_w
        if self.weights is not None:
            g, h = g * self.weights, h * self.weights
        return g, h

    def boost_from_score(self, class_id=0):
        lab = self._np_label.astype(np.float64)
        pos = (lab > 0).astype(np.float64)
        if self._np_weights is not None:
            w = self._np_weights.astype(np.float64)
            pavg = (pos * w).sum() / w.sum()
        else:
            pavg = pos.mean()
        pavg = min(max(pavg, kEpsilon), 1.0 - kEpsilon)
        return math.log(pavg / (1.0 - pavg)) / self.cfg.sigmoid

    def convert_output(self, raw):
        return 1.0 / (1.0 + np.exp(-self.cfg.sigmoid * raw))

    def class_need_train(self, class_id):
        return self.need_train


# ------------------------------ multiclass --------------------------------

class MulticlassSoftmax(ObjectiveFunction):
    """`src/objective/multiclass_objective.hpp:16-160` — K trees/iteration
    over a shared softmax; gradients for all classes computed at once."""
    name = "multiclass"

    def __init__(self, cfg):
        super().__init__(cfg)
        self.num_class = cfg.num_class
        self.num_model_per_iteration = cfg.num_class

    def init(self, metadata, num_data, num_data_padded):
        super().init(metadata, num_data, num_data_padded)
        li = self._np_label.astype(np.int32)
        if li.min() < 0 or li.max() >= self.num_class:
            raise ValueError(f"Label must be in [0, {self.num_class})")
        onehot = np.zeros((self.num_class, num_data_padded), dtype=np.float32)
        onehot[li, np.arange(len(li))] = 1.0
        self.label_onehot = jnp.asarray(onehot)
        probs = onehot[:, :num_data].sum(1)
        if self._np_weights is not None:
            probs = np.array([ (self._np_weights * (li == k)).sum()
                               for k in range(self.num_class)])
            probs = probs / self._np_weights.sum()
        else:
            probs = probs / num_data
        self.class_init_probs = probs

    def get_gradients_all(self, score_kn: jnp.ndarray):
        """score (K, N) → grads/hess (K, N) (`multiclass_objective.hpp:67-112`)."""
        p = jax.nn.softmax(score_kn, axis=0)
        g = p - self.label_onehot
        h = 2.0 * p * (1.0 - p)
        if self.weights is not None:
            g, h = g * self.weights[None, :], h * self.weights[None, :]
        return g, h

    def get_gradients(self, score, class_id=0):
        raise RuntimeError("multiclass gradients are computed jointly; "
                           "use get_gradients_all")

    def convert_output(self, raw):
        # raw (n, K) → softmax rows
        e = np.exp(raw - raw.max(axis=-1, keepdims=True))
        return e / e.sum(axis=-1, keepdims=True)


class MulticlassOVA(ObjectiveFunction):
    """`multiclass_objective.hpp:166-230` — K independent sigmoid binaries."""
    name = "multiclassova"

    def __init__(self, cfg):
        super().__init__(cfg)
        self.num_class = cfg.num_class
        self.num_model_per_iteration = cfg.num_class
        self.binaries = []

    def init(self, metadata, num_data, num_data_padded):
        super().init(metadata, num_data, num_data_padded)
        li = self._np_label.astype(np.int32)
        self.binaries = []
        for k in range(self.num_class):
            sub = BinaryLogloss(self.cfg)
            meta_k = Metadata(len(li))
            meta_k.set_label((li == k).astype(np.float32))
            if self._np_weights is not None:
                meta_k.set_weights(self._np_weights)
            sub.init(meta_k, num_data, num_data_padded)
            self.binaries.append(sub)

    def get_gradients(self, score, class_id=0):
        return self.binaries[class_id].get_gradients(score)

    def boost_from_score(self, class_id=0):
        return self.binaries[class_id].boost_from_score()

    def convert_output(self, raw):
        return 1.0 / (1.0 + np.exp(-self.cfg.sigmoid * raw))


# ----------------------------- cross entropy ------------------------------

class CrossEntropy(ObjectiveFunction):
    """`src/objective/xentropy_objective.hpp:38-137` (labels in [0,1])."""
    name = "cross_entropy"

    def get_gradients(self, score, class_id=0):
        z = _sigmoid(score)
        g = z - self.label
        h = z * (1.0 - z)
        if self.weights is not None:
            g, h = g * self.weights, h * self.weights
        return g, h

    def boost_from_score(self, class_id=0):
        lab = self._np_label.astype(np.float64)
        if self._np_weights is not None:
            w = self._np_weights.astype(np.float64)
            pavg = (lab * w).sum() / w.sum()
        else:
            pavg = lab.mean()
        pavg = min(max(pavg, kEpsilon), 1.0 - kEpsilon)
        return math.log(pavg / (1.0 - pavg))

    def convert_output(self, raw):
        return 1.0 / (1.0 + np.exp(-raw))

    def to_string(self):
        return "xentropy"


class CrossEntropyLambda(ObjectiveFunction):
    """`xentropy_objective.hpp:142-245`."""
    name = "cross_entropy_lambda"

    def get_gradients(self, score, class_id=0):
        if self.weights is None:
            z = _sigmoid(score)
            return z - self.label, z * (1.0 - z)
        w, y = self.weights, self.label
        epf = jnp.exp(score)
        hhat = jnp.log1p(epf)
        z = 1.0 - jnp.exp(-w * hhat)
        enf = 1.0 / epf
        g = (1.0 - y / z) * w / (1.0 + enf)
        c = 1.0 / (1.0 - z)
        d = 1.0 + epf
        a = w * epf / (d * d)
        d2 = c - 1.0
        b = (c / (d2 * d2)) * (1.0 + w * epf - c)
        h = a * (1.0 + y * b)
        return g, h

    def boost_from_score(self, class_id=0):
        lab = self._np_label.astype(np.float64)
        if self._np_weights is not None:
            w = self._np_weights.astype(np.float64)
            pavg = (lab * w).sum() / w.sum()
        else:
            pavg = lab.mean()
        pavg = min(max(pavg, kEpsilon), 1.0 - kEpsilon)
        return math.log(pavg / (1.0 - pavg))

    def convert_output(self, raw):
        return np.log1p(np.exp(raw))

    def to_string(self):
        return "xentlambda"


# ---------------------------- percentile utils -----------------------------

def _percentile(values: np.ndarray, alpha: float) -> float:
    """``PercentileFun`` (`regression_objective.hpp:23-37`)."""
    if len(values) <= 1:
        return float(values[0]) if len(values) else 0.0
    position = (len(values) - 1) * alpha
    pos_int = int(position)
    srt = np.sort(values)
    if pos_int == position:
        return float(srt[pos_int])
    frac = position - pos_int
    return float(srt[pos_int] * (1 - frac) + srt[pos_int + 1] * frac)


def _weighted_percentile(values: np.ndarray, weights: np.ndarray,
                         alpha: float) -> float:
    """``WeightedPercentileFun`` (`regression_objective.hpp:39-69`)."""
    if len(values) == 0:
        return 0.0
    if len(values) == 1:
        return float(values[0])
    order = np.argsort(values)
    v, w = np.asarray(values)[order], np.asarray(weights, dtype=np.float64)[order]
    cum = np.cumsum(w) - w * 0.5
    threshold = alpha * w.sum()
    idx = int(np.searchsorted(cum, threshold, side="right")) - 1
    idx = max(0, min(idx, len(v) - 2))
    if cum[idx + 1] <= threshold:
        idx += 1
    if idx == len(v) - 1:
        return float(v[-1])
    frac = (threshold - cum[idx]) / max(cum[idx + 1] - cum[idx], 1e-300)
    return float(v[idx] * (1 - frac) + v[idx + 1] * frac)


def _percentile_renew(tree, label, weights, score, leaf_id, mask, alpha):
    """``RenewTreeOutput`` for the L1 family
    (`regression_objective.hpp:224-298`): set each leaf's output to the alpha
    percentile of (label - score) over its (bagged) rows."""
    n = len(label)
    leaf_id = np.asarray(leaf_id)[:n]
    mask = np.asarray(mask)[:n] > 0
    resid = label.astype(np.float64) - np.asarray(score)[:n]
    for leaf in range(tree.num_leaves):
        sel = (leaf_id == leaf) & mask
        if not sel.any():
            continue
        if weights is None:
            out = _percentile(resid[sel], alpha)
        else:
            out = _weighted_percentile(resid[sel], np.asarray(weights)[sel], alpha)
        tree.set_leaf_output(leaf, out)


# ------------------------------- factory -----------------------------------

def create_objective(cfg: Config) -> Optional[ObjectiveFunction]:
    """`src/objective/objective_function.cpp:10-82`."""
    from .rank_objective import LambdarankNDCG
    table = {
        "regression": RegressionL2, "regression_l1": RegressionL1,
        "huber": RegressionHuber, "fair": RegressionFair,
        "poisson": RegressionPoisson, "quantile": RegressionQuantile,
        "mape": RegressionMAPE, "gamma": RegressionGamma,
        "tweedie": RegressionTweedie, "binary": BinaryLogloss,
        "multiclass": MulticlassSoftmax, "multiclassova": MulticlassOVA,
        "cross_entropy": CrossEntropy, "cross_entropy_lambda": CrossEntropyLambda,
        "lambdarank": LambdarankNDCG,
    }
    if cfg.objective in ("none", "null", "custom", "na", ""):
        return None
    if cfg.objective not in table:
        raise ValueError(f"Unknown objective type name: {cfg.objective}")
    return table[cfg.objective](cfg)
