"""GBDT — the main boosting loop.

TPU-native re-design of ``GBDT`` (`src/boosting/gbdt.{h,cpp}`): the Python
host drives iterations while every O(N) step — gradient computation, bagged
histogram trees, score updates, validation-score tree traversal — runs as
jitted device work over the padded row axis.

Loop structure mirrors ``GBDT::TrainOneIter`` (`gbdt.cpp:333-413`):
boost-from-average (`gbdt.cpp:309-331`), gradients (`gbdt.cpp:149-157`),
bagging (`gbdt.cpp:180-241`), per-class tree training, objective leaf
renewal, shrinkage, score update (`gbdt.cpp:451-474`), metric output with
early-stopping bookkeeping (`gbdt.cpp:476-533`), and the ``AddBias`` /
``AsConstantTree`` init-score folding.  Model text serialization follows
`src/boosting/gbdt_model_text.cpp:244-341`.
"""

from __future__ import annotations

import math
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..binning import kEpsilon
from ..config import Config
from ..dataset import Dataset, _ConstructedDataset
from ..learner import TPUTreeLearner
from ..metrics import Metric, create_metric
from ..objectives import ObjectiveFunction, create_objective
from ..observability.phases import scope
from ..ops.lookup import lookup_leaf_values
from ..tree import Tree

K_MODEL_VERSION = "v2"


class ScoreUpdater:
    """Running raw scores for one dataset (`src/boosting/score_updater.hpp`).
    Scores live on device as (K, N_pad) f32."""

    def __init__(self, data: _ConstructedDataset, num_class: int):
        self.data = data
        self.num_class = num_class
        self.num_data = data.num_data
        n_pad = data.num_data_padded
        score = np.zeros((num_class, n_pad), dtype=np.float32)
        self.has_init_score = False
        init = data.metadata.init_score
        if init is not None:
            self.has_init_score = True
            init = np.asarray(init, dtype=np.float32)
            if len(init) == self.num_data * num_class:
                score[:, :self.num_data] = init.reshape(num_class, self.num_data)
            else:
                score[:, :self.num_data] = init[None, :self.num_data]
        self.score = jnp.asarray(score)
        self._bins_cache = None

    def add_constant(self, val: float, class_id: int) -> None:
        self.score = self.score.at[class_id].add(np.float32(val))

    def add_by_leaf_id(self, leaf_values: np.ndarray, leaf_id: jax.Array,
                       class_id: int) -> None:
        """Train-side update: look the (host-renewed, shrunk) leaf values up
        by the learner's final leaf partition (`score_updater.hpp:74-96`).

        On a TPU the per-row lookup is a chunked one-hot contraction, not an
        XLA gather (`ops/lookup.py: lookup_leaf_values` has the readings and
        the rule); the results are bit-identical either way."""
        lv = jnp.asarray(leaf_values.astype(np.float32))
        upd = lookup_leaf_values(lv, leaf_id, _row_sharded(leaf_id))
        self.score = self.score.at[class_id].add(upd)

    def add_by_tree(self, tree: Tree, class_id: int) -> None:
        """Valid-side update: traverse the tree over this dataset's binned
        matrix on device (`score_updater.hpp:97-105` AddScore(tree))."""
        if tree.num_leaves <= 1:
            self.add_constant(float(tree.leaf_value[0]), class_id)
            return
        delta = _traverse_tree_binned(self.data, tree)
        self.score = self.score.at[class_id].add(delta)

    def np_score(self) -> np.ndarray:
        """(n, K) raw scores on host (unpadded)."""
        s = np.asarray(self.score)[:, :self.num_data]
        return s.T if self.num_class > 1 else s[0]


def rebind_tree_to_dataset(tree: Tree, data: _ConstructedDataset) -> None:
    """Reconstruct the inner (bin-space) split fields of a deserialized tree
    — ``split_feature_inner`` / ``threshold_in_bin`` are not part of the model
    text format (`src/io/tree.cpp:207-240`); the reference rebuilds them on
    load the same way (real feature index → used-feature slot, real threshold
    → bin via the mapper's upper bounds)."""
    if not getattr(tree, "needs_rebind", False):
        return
    from ..tree import _in_bitset

    real2inner = {int(j): k for k, j in enumerate(data.used_feature_map)}
    tree._cat_bitsets_inner = {}
    for nd in range(tree.num_leaves - 1):
        real = int(tree.split_feature[nd])
        inner = real2inner.get(real)
        if inner is None:
            raise ValueError(
                f"Model splits on feature {real} which is trivial/unused in "
                "the training data; cannot continue training on this dataset")
        tree.split_feature_inner[nd] = inner
        if not (tree.decision_type[nd] & 1):  # numerical
            tree.threshold_in_bin[nd] = data.bin_mappers[inner].value_to_bin(
                float(tree.threshold[nd]))
        else:
            # categorical: rebuild the inner (bin-space) bitset from the
            # stored category-value bitset via the mapper
            cat_idx = int(tree.threshold[nd])
            tree.threshold_in_bin[nd] = cat_idx
            lo, hi = tree.cat_boundaries[cat_idx], \
                tree.cat_boundaries[cat_idx + 1]
            mapper = data.bin_mappers[inner]
            bins = {mapper.categorical_2_bin[c]
                    for c in mapper.categorical_2_bin
                    if c >= 0 and _in_bitset(tree.cat_threshold, lo, hi, c)}
            tree._cat_bitsets_inner[cat_idx] = bins
    # the cached traversal pack (if any) was built from the previous bin
    # space — the bin-space transition owns its invalidation
    if hasattr(tree, "_traverse_pack"):
        del tree._traverse_pack
    tree.needs_rebind = False


def _traverse_tree_binned(data: _ConstructedDataset, tree: Tree) -> jax.Array:
    """Vectorized inner-bin traversal (``NumericalDecisionInner``,
    `tree.h:233-249`) over all rows of a binned dataset.

    The per-node device arrays depend only on the tree and the bin mappers,
    so they are cached per bin-space (reference-linked valid sets share the
    train set's mapper list, `dataset.py:329`, and reuse one pack) — a
    train/valid/train alternation does not rebuild.
    """
    import weakref

    ni = tree.num_leaves - 1
    packs = getattr(tree, "_traverse_pack", None)
    if packs is None or packs[0] != tree.num_leaves:
        packs = (tree.num_leaves, {})
        tree._traverse_pack = packs
    # keyed by the mapper list's id (reference-linked valid sets share the
    # train set's list and reuse one pack), guarded by a weakref to a dataset
    # owning that list so a recycled address after GC can never serve a
    # stale bin space
    key = id(data.bin_mappers)
    entry = packs[1].get(key)
    pack = None
    if entry is not None:
        owner = entry[0]()
        if owner is not None and owner.bin_mappers is data.bin_mappers:
            pack = entry[1]
    if pack is None:
        num_bin, missing, default_bin, _ = data.feature_meta_arrays()
        feat = tree.split_feature_inner[:ni]
        depth = int(tree.leaf_depth[:tree.num_leaves].max())
        w = (int(data.max_num_bin) + 31) // 32
        is_cat_n = (tree.decision_type[:ni] & 1) != 0
        cat_bits = np.zeros((ni, w), dtype=np.uint32)
        if is_cat_n.any():
            inner_sets = getattr(tree, "_cat_bitsets_inner", {})
            for nd in np.where(is_cat_n)[0]:
                for b in inner_sets.get(int(tree.threshold_in_bin[nd]), ()):
                    cat_bits[nd, b // 32] |= np.uint32(1 << (b % 32))
        pack = (depth,
                jnp.asarray(feat), jnp.asarray(tree.threshold_in_bin[:ni]),
                jnp.asarray(missing[feat]), jnp.asarray(default_bin[feat]),
                jnp.asarray(num_bin[feat] - 1),
                jnp.asarray((tree.decision_type[:ni] & 2) != 0),
                jnp.asarray(tree.left_child[:ni]),
                jnp.asarray(tree.right_child[:ni]),
                jnp.asarray(is_cat_n), jnp.asarray(cat_bits))
        packs[1][key] = (weakref.ref(data), pack)
    depth, feat, thr, node_missing, node_default_bin, node_nan_bin, \
        node_default_left, left_child, right_child, node_is_cat, \
        node_cat_bits = pack
    # leaf values change under DART re-shrinkage, so always ship them fresh
    leaf_value = jnp.asarray(tree.leaf_value[:tree.num_leaves]
                             .astype(np.float32))
    return _traverse_jit(
        data.device_bins(), feat, thr, node_missing, node_default_bin,
        node_nan_bin, node_default_left, left_child, right_child,
        node_is_cat, node_cat_bits, leaf_value, depth)


import functools


@functools.partial(jax.jit, static_argnames=("depth",))
def _traverse_jit(bins, feat, thr, node_missing, node_default_bin,
                  node_nan_bin, node_default_left, left_child, right_child,
                  node_is_cat, node_cat_bits, leaf_value, depth):
    n = bins.shape[1]
    node = jnp.zeros(n, dtype=jnp.int32)
    rows = jnp.arange(n)

    def step(node, _):
        nd = jnp.maximum(node, 0)  # leaves encoded negative; keep stable
        f = feat[nd]
        fv = bins[f, rows].astype(jnp.int32)
        mt = node_missing[nd]
        is_missing = ((mt == 1) & (fv == node_default_bin[nd])) | \
                     ((mt == 2) & (fv == node_nan_bin[nd]))
        go_left = jnp.where(is_missing, node_default_left[nd], fv <= thr[nd])
        # categorical nodes: bitset membership (CategoricalDecisionInner)
        word = jnp.take_along_axis(node_cat_bits[nd], (fv >> 5)[:, None],
                                   axis=1)[:, 0]
        cat_left = ((word >> (fv & 31).astype(jnp.uint32)) & 1).astype(bool)
        go_left = jnp.where(node_is_cat[nd], cat_left, go_left)
        nxt = jnp.where(go_left, left_child[nd], right_child[nd])
        return jnp.where(node < 0, node, nxt), None

    node, _ = jax.lax.scan(step, node, None, length=depth)
    leaf = jnp.where(node < 0, ~node, 0)
    return leaf_value[leaf]


def _row_sharded(a: jax.Array) -> bool:
    """Whether ``a`` (a learner's ``leaf_id``) lives on more than one device."""
    return len(a.sharding.device_set) > 1


@functools.partial(jax.jit, static_argnames=("k", "row_sharded"),
                   donate_argnums=(0,))
def _score_add_leaf(score, leaf_output, leaf_id, lr, k, row_sharded=False):
    """Device-side training-score update from the learner's final leaf
    partition — the sync-free fast path of ``ScoreUpdater.add_by_leaf_id``."""
    with scope("score_update"):
        return score.at[k].add(
            lr * lookup_leaf_values(leaf_output, leaf_id, row_sharded))


class GBDT:
    """Reference `src/boosting/gbdt.h:24`.

    The boosting loop is PIPELINED when the objective doesn't renew leaf
    outputs and there are no validation sets: every per-iteration step
    (gradients, tree build, score update) stays on device with zero host
    syncs, and the small per-split record arrays are fetched lazily — host
    trees are assembled only when something actually reads ``self.models``
    (eval, save, predict).  On a remote-attached TPU this removes the
    dominant cost of an iteration (host round trips), the analogue of the
    reference keeping its whole iteration inside the OpenMP region.
    """

    name = "gbdt"
    _supports_pipeline = True

    def __init__(self, cfg: Config, train_data: Optional[Dataset] = None,
                 objective: Optional[ObjectiveFunction] = None):
        self.cfg = cfg
        self.iter_ = 0
        from ..observability import SampledSync, Telemetry
        self.telemetry = Telemetry(bool(getattr(cfg, "telemetry", False)))
        # sampled-sync attribution bracket (observability/attribution.py):
        # inert unless telemetry AND telemetry_sync_every > 0
        self._sync_sampler = SampledSync(
            self.telemetry, int(getattr(cfg, "telemetry_sync_every", 0)))
        self._pending: List[tuple] = []
        self._stopped = False
        self._model_version = 0          # bumped on in-place tree mutation
        self._device_predictor = None    # (key, DevicePredictor) cache
        self._pred_schema = None         # 1-tuple cache (loaded boosters)
        self._jit_grad_fn = None
        self._lr_dev = None
        self._lr_dev_val = None
        self.models: List[Tree] = []
        self.train_data: Optional[_ConstructedDataset] = None
        self.objective = objective
        self.num_tree_per_iteration = 1
        self.shrinkage_rate = cfg.learning_rate
        self.max_feature_idx = 0
        self.label_idx = 0
        self.feature_names: List[str] = []
        self.feature_infos: List[str] = []
        self.learner: Optional[TPUTreeLearner] = None
        self.train_score: Optional[ScoreUpdater] = None
        self.valid_scores: List[ScoreUpdater] = []
        self.valid_names: List[str] = []
        self.training_metrics: List[Metric] = []
        self.valid_metrics: List[List[Metric]] = []
        self.best_score: List[List[float]] = []
        self.best_iter: List[List[int]] = []
        self.best_msg: List[List[str]] = []
        self.class_need_train: List[bool] = []
        self._bag_rng = np.random.RandomState(cfg.bagging_seed)
        self._feat_rng = np.random.RandomState(cfg.feature_fraction_seed)
        self.loaded_parameter = ""
        self.average_output = False
        self.pandas_categorical: Optional[list] = None
        self.eval_history: Dict[str, Dict[str, List[float]]] = {}
        if train_data is not None:
            self.init(train_data, objective)

    # -- pipelined tree materialization --------------------------------------

    @property
    def models(self) -> List[Tree]:
        self._flush_pending()
        return self._models

    @models.setter
    def models(self, value) -> None:
        self._flush_pending()
        self._models = list(value)

    def _flush_pending(self, keep: int = 0) -> None:
        """Assemble host trees for pipelined iterations dispatched so
        far, then run the deferred no-more-splits stop check
        (`gbdt.cpp:379-387` in the sync loop).

        ``keep`` leaves the newest ``keep`` queue entries un-assembled —
        the cross-iteration pipelining seam: the boosting loop flushes
        with ``keep = tpu_pipeline_flush_depth`` every iteration, so each
        step assembles exactly ONE tree whose device program retired many
        iterations ago (its record copies are host-resident) while the
        devices keep executing the queued tail.  The round-5 batch flush
        (keep=0 every 16th iteration) drained the whole queue in one
        device-idle stall — 15-25 ms/tree of host assembly plus the queue
        sync, the largest non-device cost in the trace."""
        pend = getattr(self, "_pending", None)
        if not pend or len(pend) <= keep:
            return
        if keep > 0:
            pend, self._pending = pend[:-keep], pend[-keep:]
        else:
            self._pending = []
        tel = self.telemetry
        with tel.phase("flush", it=self.iter_ - 1, trees=len(pend)):
            # the record arrays were copy_to_host_async'd at dispatch time,
            # so _assemble_entry's np.asarray calls find host-resident
            # data; only records of still-executing queued trees block, on
            # execution itself
            first_idx = len(self._models)
            for entry in pend:
                first_idx = min(first_idx, self._assemble_entry(entry))
            # deferred stop detection over the flushed iterations only: the
            # first iteration in which NO class grew a tree ends training;
            # later iterations repeated the draw and are dropped
            # (`gbdt.cpp:379-387`), including rolling their contributions
            # back out of the training score (under bagging a later draw
            # may have split)
            k = max(self.num_tree_per_iteration, 1)
            for it in range(first_idx // k, len(self._models) // k):
                trees = self._models[it * k:(it + 1) * k]
                if trees and all(t is not None and t.num_leaves <= 1
                                 for t in trees):
                    # a rolling flush may still hold queued post-stop
                    # iterations whose device score updates already applied
                    # — drain them so the rollback below covers every tree
                    if self._pending:
                        tail, self._pending = self._pending, []
                        for entry in tail:
                            self._assemble_entry(entry)
                    # keep iteration 0's constant trees (the sync path's
                    # first-iteration case keeps them too); everything after
                    # the stop iteration is rolled back and dropped
                    drop_from = max(it, 1) * k
                    for di in range(drop_from, len(self._models)):
                        t = self._models[di]
                        if t is not None and t.num_leaves > 1:
                            t.apply_shrinkage(-1.0)
                            delta = _traverse_tree_binned(
                                self.train_data, t)
                            self.train_score.score = self.train_score \
                                .score.at[di % k].add(delta)
                    del self._models[drop_from:]
                    self.iter_ = it
                    self._stopped = True
                    import warnings
                    warnings.warn(
                        "Stopped training because there are no more "
                        "leaves that meet the split requirements")
                    break
        if tel.enabled:
            tel.inc("pipeline_flushes")
            tel.inc("trees_assembled", len(pend))
            if keep == 0:
                # the per-tree device counter vectors rode the same async
                # copies as the records — decode them now, off the hot
                # path (a rolling flush keeps queued trees executing, so
                # their counters are decoded at the next full flush)
                tel.flush_device()

    def _assemble_entry(self, entry) -> int:
        """Materialize one queued pipelined tree into ``self._models``;
        returns its model index."""
        idx, rf, ri, rc, init_sc = entry
        tel = self.telemetry
        # where the host blocks on the device: the records of a tree whose
        # program has retired are host-resident (copy_to_host_async at
        # dispatch); those of a still-queued tree wait for its execution
        with tel.phase("d2h_wait", tree=idx):
            rf, ri, rc = np.asarray(rf), np.asarray(ri), np.asarray(rc)
        with tel.phase("assemble_tree", tree=idx):
            tree = self.learner.assemble_host(rf, ri, rc)
            if tree.num_leaves > 1:
                tree.apply_shrinkage(self.shrinkage_rate)
                if abs(init_sc) > kEpsilon:
                    tree.leaf_value[:tree.num_leaves] += init_sc
                    tree.shrinkage = 1.0
            elif idx < self.num_tree_per_iteration:
                # nothing splittable on the very first iteration: keep the
                # boost-from-average constant model and add its output to
                # the training score, matching the sync path
                # (`gbdt.cpp:395-404`)
                tree.leaf_value[0] = init_sc
                if abs(init_sc) > kEpsilon:
                    self.train_score.add_constant(
                        init_sc, idx % self.num_tree_per_iteration)
        self._models[idx] = tree
        return idx

    # -- GBDT::Init (`gbdt.cpp:45-137`) -------------------------------------

    def init(self, train_data: Dataset, objective: Optional[ObjectiveFunction],
             training_metrics: Sequence[Metric] = ()) -> None:
        data = train_data.constructed
        self.train_data = data
        self.objective = objective
        self.num_tree_per_iteration = (
            objective.num_model_per_iteration if objective is not None
            else max(self.cfg.num_class, 1))
        if objective is not None:
            objective.init(data.metadata, data.num_data, data.num_data_padded)
        from ..learner_compact import create_tree_learner
        self.learner = create_tree_learner(self.cfg, data)
        if self.cfg.forcedsplits_filename and \
                hasattr(self.learner, "set_forced_splits"):
            from ..forced import load_forced_splits
            forced = load_forced_splits(self.cfg.forcedsplits_filename, data)
            if forced and len(forced) > self.cfg.num_leaves - 1:
                import warnings
                warnings.warn(
                    f"forced-splits tree has {len(forced)} splits but "
                    f"num_leaves={self.cfg.num_leaves} allows "
                    f"{self.cfg.num_leaves - 1}; truncating in BFS order")
                forced = forced[:self.cfg.num_leaves - 1]
            self.learner.set_forced_splits(forced)
        self.train_score = ScoreUpdater(data, self.num_tree_per_iteration)
        self.training_metrics = list(training_metrics)
        self.max_feature_idx = data.num_total_features - 1
        self.feature_names = list(data.feature_names)
        self.feature_infos = _feature_infos(data)
        self.pandas_categorical = getattr(train_data, "pandas_categorical",
                                          None)
        self.class_need_train = [
            objective.class_need_train(k) if objective is not None else True
            for k in range(self.num_tree_per_iteration)]
        n_pad = data.num_data_padded
        base = np.zeros(n_pad, dtype=np.float32)
        base[:data.num_data] = 1.0
        self._valid_rows = jnp.asarray(base)     # 0 on padded rows
        self.num_data = data.num_data
        self._bag_mask = self._valid_rows
        self._bag_cnt = data.num_data
        self._np_bag_mask = np.asarray(base)
        # parallel tree learning: shard over the local mesh so the jitted
        # steps compile under GSPMD with ICI collectives
        # (`tree_learner=data|feature|voting`, SURVEY §2.7)
        self._mesh = None
        self._parallel_mode = None
        if self.cfg.tree_learner in ("data", "feature", "voting",
                                     "data_feature"):
            if len(jax.devices()) > 1:
                from ..parallel.learners import apply_parallel_sharding
                # multihost.mesh_for_config == sharding.mesh_for_config on
                # one host; on a pod it resolves the parallel_mesh grammar
                # over the GLOBAL device list and host-alignment-checks
                # the row axis
                from ..parallel.multihost import mesh_for_config
                apply_parallel_sharding(self, mesh_for_config(self.cfg),
                                        self.cfg.tree_learner)
            else:
                # legal (the CPU tests run parallel configs on whatever
                # is visible) but never silent: a job that asked for a
                # mesh and got one device trains serial
                import warnings
                warnings.warn(
                    f"tree_learner={self.cfg.tree_learner} was requested "
                    f"but only ONE device is visible "
                    f"({jax.devices()[0]}): training SERIAL on it")
        # the learner the job was routed to puts the table on its device(s)
        # now, before anything is traced
        self.learner.place_table()

    def add_valid_data(self, valid_data: Dataset, name: str,
                       metrics: Sequence[Metric]) -> None:
        data = valid_data.constructed
        self.valid_scores.append(ScoreUpdater(data, self.num_tree_per_iteration))
        self.valid_names.append(name)
        self.valid_metrics.append(list(metrics))
        self.best_score.append([-math.inf] * len(metrics))
        self.best_iter.append([0] * len(metrics))
        self.best_msg.append([""] * len(metrics))

    # -- bagging (`gbdt.cpp:180-241`, `ResetBaggingConfig` `gbdt.cpp:689`) ---

    def _place_rows(self, arr: np.ndarray) -> jax.Array:
        """Upload a row-aligned vector, sharded like the training rows."""
        if self._mesh is not None and self._parallel_mode in \
                ("data", "voting", "data_feature"):
            from jax.sharding import NamedSharding, PartitionSpec as P
            from ..parallel.sharding import row_axis
            return jax.device_put(arr, NamedSharding(
                self._mesh, P(row_axis(self._mesh))))
        return jnp.asarray(arr)

    def _bagging(self, iter_: int) -> None:
        cfg = self.cfg
        if cfg.bagging_freq > 0 and cfg.bagging_fraction < 1.0 \
                and iter_ % cfg.bagging_freq == 0:
            with self.telemetry.phase("bagging", it=iter_):
                n = self.num_data
                bag_cnt = int(cfg.bagging_fraction * n)
                idx = self._bag_rng.choice(n, bag_cnt, replace=False)
                mask = np.zeros(self.train_data.num_data_padded,
                                dtype=np.float32)
                mask[idx] = 1.0
                self._bag_mask = self._place_rows(mask)
                self._np_bag_mask = mask
                self._bag_cnt = bag_cnt

    def _np_bag(self) -> np.ndarray:
        """Host copy of the bagging mask, materialized lazily (device-side
        samplers like GOSS leave it None until a renew path needs it)."""
        if self._np_bag_mask is None:
            self._np_bag_mask = np.asarray(self._bag_mask)
        return self._np_bag_mask

    def _feature_sample(self) -> jax.Array:
        """Per-tree feature_fraction sampling (`serial_tree_learner.cpp:255-283`)."""
        with self.telemetry.phase("feature_sample", it=self.iter_):
            f = self.train_data.num_used_features
            frac = self.cfg.feature_fraction
            if frac >= 1.0:
                if getattr(self, "_full_fmask", None) is None \
                        or self._full_fmask.shape[0] != f:
                    self._full_fmask = jnp.ones(f, dtype=bool)
                return self._full_fmask
            used = max(1, int(round(f * frac)))
            idx = self._feat_rng.choice(f, used, replace=False)
            mask = np.zeros(f, dtype=bool)
            mask[idx] = True
            return jnp.asarray(mask)

    # -- gradients -----------------------------------------------------------

    #: objective attributes that hold row-aligned device arrays — the same
    #: list `parallel/learners.py` shards over the mesh
    _OBJ_ARRAYS = ("label", "weights", "trans_label", "label_sign",
                   "label_w", "label_weight", "label_onehot")

    def _compute_gradients(self) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """(K, N_pad) gradients/hessians from the objective (`gbdt.cpp:149`),
        as ONE jitted dispatch.  The objective's row-aligned arrays enter as
        jit ARGUMENTS, not closure constants: under a multi-process mesh
        (`parallel/multihost.py`) they span non-addressable devices, and
        closing over such an array is an error — passing them as args is
        equivalent (they are fixed for the life of the booster) and legal
        everywhere."""
        if self._jit_grad_fn is None:
            obj = self.objective
            K = self.num_tree_per_iteration

            def grad_all(score, arrs):
                saved = {n: getattr(obj, n) for n in arrs}
                for n, v in arrs.items():
                    setattr(obj, n, v)
                try:
                    with scope("gradients"):
                        if obj.name == "multiclass":
                            return obj.get_gradients_all(score)
                        gs, hs = [], []
                        for k in range(K):
                            g, h = obj.get_gradients(score[k], k)
                            gs.append(g)
                            hs.append(h)
                        return jnp.stack(gs), jnp.stack(hs)
                finally:
                    for n, v in saved.items():
                        setattr(obj, n, v)

            self._jit_grad_fn = jax.jit(grad_all)
        obj = self.objective
        arrs = {n: getattr(obj, n) for n in self._OBJ_ARRAYS
                if getattr(obj, n, None) is not None
                and hasattr(getattr(obj, n), "shape")}
        t0 = time.perf_counter()
        with self.telemetry.phase("gradients", it=self.iter_):
            g, h = self._jit_grad_fn(self.train_score.score, arrs)
        self._sync_sampler.leg("gradients", t0, (g, h))
        return g, h

    # -- one boosting iteration (`gbdt.cpp:333-413`) -------------------------

    def _pad_external_gradients(self, gradients, hessians):
        grad = jnp.asarray(np.asarray(gradients, dtype=np.float32)
                           .reshape(self.num_tree_per_iteration, -1))
        hess = jnp.asarray(np.asarray(hessians, dtype=np.float32)
                           .reshape(self.num_tree_per_iteration, -1))
        if grad.shape[1] != self.train_data.num_data_padded:
            pad = self.train_data.num_data_padded - grad.shape[1]
            grad = jnp.pad(grad, ((0, 0), (0, pad)))
            hess = jnp.pad(hess, ((0, 0), (0, pad)))
        return grad, hess

    def train_one_iter(self, gradients: Optional[np.ndarray] = None,
                       hessians: Optional[np.ndarray] = None) -> bool:
        """Returns True when training cannot continue (no splittable leaves)."""
        ss = self._sync_sampler
        if ss.sampled(self.iter_):
            # sampled-sync bracket: drain the queued pipeline so the
            # measured iteration holds only its own work, sync each leg
            # (the ss.leg calls on the dispatch paths), then sync the
            # whole iteration so ``sync.iteration`` is a true wall.  All
            # ranks sample on the lockstep iteration counter, so the
            # probe's collective is entered pod-wide together.
            from ..observability import force_sync
            ss.drain(self.train_score.score)
            ss.active = True
            t0 = time.perf_counter()
            try:
                with self.telemetry.phase("iteration", it=self.iter_):
                    ret = self._train_one_iter_inner(gradients, hessians)
                    force_sync(self.train_score.score)
            finally:
                ss.active = False
            self.telemetry.add_phase_time(
                "sync.iteration", time.perf_counter() - t0, t0=t0)
            ss.probe_exchange(self.learner)
            return ret
        with self.telemetry.phase("iteration", it=self.iter_):
            return self._train_one_iter_inner(gradients, hessians)

    def _train_one_iter_inner(self, gradients=None, hessians=None) -> bool:
        if self._stopped:
            return True
        init_scores = [0.0] * self.num_tree_per_iteration
        if gradients is None or hessians is None:
            for k in range(self.num_tree_per_iteration):
                init_scores[k] = self._boost_from_average(k, update_scorer=True)
            if self._can_fuse():
                # gradients are computed INSIDE the fused program
                self._bagging(self.iter_)
                return self._train_trees_fused(init_scores)
            grad, hess = self._compute_gradients()
        else:
            grad, hess = self._pad_external_gradients(gradients, hessians)
        self._bagging(self.iter_)
        return self._train_trees(grad, hess, init_scores)

    def _can_fuse(self) -> bool:
        """One jit program per iteration (gradients -> tree -> score
        update): removes two dispatch gaps and the grad/hess HBM
        round-trip.  Plain single-class GBDT on the
        serial compact/wave learners only — GOSS/DART reorder around
        gradients, and the sharded learners own their shard_map programs."""
        from ..learner_compact import CompactTPUTreeLearner
        return (self.name == "gbdt"
                and self.num_tree_per_iteration == 1
                and self._can_pipeline()
                and type(self.learner).__module__.startswith(
                    "lightgbm_tpu.learner")
                and isinstance(self.learner, CompactTPUTreeLearner))

    def _fused_iter_fn(self):
        if getattr(self, "_jit_fused", None) is None:
            obj = self.objective
            learner = self.learner
            from ..learner_wave import WaveTPUTreeLearner
            tree_fn = learner._train_tree_wave \
                if isinstance(learner, WaveTPUTreeLearner) \
                else learner._train_tree_compact

            def step(score, bins_p, bag, fmask, lr):
                # device phase scopes (observability/phases.py): names in
                # every enclosed operation's op_name, no equation
                with scope("gradients"):
                    g, h = obj.get_gradients(score[0], 0)
                out = tree_fn(bins_p, g, h, bag, fmask)
                rec_f, rec_i, rec_cat, leaf_id, leaf_out = out[:5]
                with scope("score_update"):
                    score = score.at[0].add(
                        lr * lookup_leaf_values(leaf_out, leaf_id))
                # out[5:] is the telemetry counter lane (present only when
                # cfg.telemetry — the program is unchanged otherwise)
                return (score, rec_f, rec_i, rec_cat) + tuple(out[5:])

            self._jit_fused = jax.jit(step, donate_argnums=(0,))
        return self._jit_fused

    def _train_trees_fused(self, init_scores) -> bool:
        tel = self.telemetry
        if self.shrinkage_rate != self._lr_dev_val:
            self._lr_dev = jnp.float32(self.shrinkage_rate)
            self._lr_dev_val = self.shrinkage_rate
        fmask = self._feature_sample()
        _t0 = time.perf_counter()
        with tel.phase("dispatch", it=self.iter_,
                       queued=len(self._pending)):
            out = self._fused_iter_fn()(
                self.train_score.score, self.learner.bins_packed(),
                self._bag_mask, fmask, self._lr_dev)
        # on sampled iterations the fused program IS the whole tree leg
        # (gradients -> tree -> score update in one dispatch)
        self._sync_sampler.leg("tree_build", _t0, out)
        score, rec_f, rec_i, rec_cat = out[:4]
        telem = out[4] if len(out) > 4 else None
        self.train_score.score = score
        # start the device->host record copies NOW: they stream behind the
        # still-queued tree programs, so the 16-iteration flush finds them
        # host-resident instead of blocking on a device->host fetch
        for a in (rec_f, rec_i, rec_cat) + (() if telem is None
                                            else (telem,)):
            a.copy_to_host_async()
        tel.device_telem(telem)
        self._pending.append((len(self._models), rec_f, rec_i, rec_cat,
                              init_scores[0]))
        self._models.append(None)
        self.iter_ += 1
        # cross-iteration pipelining: assemble ONE depth-old tree per
        # iteration (host work overlaps the executing queue) instead of
        # draining 16 in a device-idle stall; depth <= 0 restores the
        # round-5 batch flush
        depth = int(getattr(self.cfg, "tpu_pipeline_flush_depth", 8))
        if depth > 0:
            self._flush_pending(keep=depth)
        elif len(self._pending) >= 16:
            self._flush_pending()
        return self._stopped

    def _can_pipeline(self) -> bool:
        return (self._supports_pipeline
                and self.objective is not None
                and not self.objective.needs_renew_tree_output
                and not self.valid_scores
                and all(self.class_need_train)
                and self.train_data.num_used_features > 0
                and hasattr(self.learner, "train_async"))

    def _train_trees_pipelined(self, grad, hess, init_scores) -> bool:
        """Sync-free iteration: tree build + device score update dispatched
        asynchronously; host trees materialize lazily in ``_flush_pending``."""
        tel = self.telemetry
        if self.shrinkage_rate != self._lr_dev_val:
            self._lr_dev = jnp.float32(self.shrinkage_rate)
            self._lr_dev_val = self.shrinkage_rate
        for k in range(self.num_tree_per_iteration):
            fmask = self._feature_sample()
            _t0 = time.perf_counter()
            with tel.phase("dispatch", it=self.iter_,
                           queued=len(self._pending)):
                rec_f, rec_i, rec_cat, leaf_id, leaf_out = \
                    self.learner.train_async(grad[k], hess[k],
                                             self._bag_mask, fmask)
            self._sync_sampler.leg(
                "tree_build", _t0, (rec_f, rec_i, rec_cat, leaf_id,
                                    leaf_out))
            _t0 = time.perf_counter()
            with tel.phase("score_update", it=self.iter_):
                self.train_score.score = _score_add_leaf(
                    self.train_score.score, leaf_out, leaf_id,
                    self._lr_dev, k, row_sharded=_row_sharded(leaf_id))
            self._sync_sampler.leg("score_update", _t0,
                                   (self.train_score.score,))
            telem = self.learner.take_telemetry() \
                if tel.enabled and hasattr(self.learner, "take_telemetry") \
                else None
            for a in (rec_f, rec_i, rec_cat) + (() if telem is None
                                                else (telem,)):
                a.copy_to_host_async()  # see _train_trees_fused
            tel.device_telem(telem)
            self._pending.append((len(self._models), rec_f, rec_i, rec_cat,
                                  init_scores[k]))
            self._models.append(None)
        self.iter_ += 1
        # bound stop-detection staleness without stalling the pipeline: the
        # arrays synced here finished many iterations ago (see
        # _train_trees_fused for the rolling-flush rationale)
        depth = int(getattr(self.cfg, "tpu_pipeline_flush_depth", 8))
        if depth > 0:
            self._flush_pending(keep=depth * self.num_tree_per_iteration)
        elif len(self._pending) >= 16 * self.num_tree_per_iteration:
            self._flush_pending()
        return self._stopped

    def _train_trees(self, grad, hess, init_scores) -> bool:
        """Per-class tree loop shared by GBDT/GOSS/DART
        (`gbdt.cpp:348-413`)."""
        if self._can_pipeline():
            return self._train_trees_pipelined(grad, hess, init_scores)
        tel = self.telemetry
        should_continue = False
        for k in range(self.num_tree_per_iteration):
            new_tree = Tree(2)
            leaf_id = None
            if self.class_need_train[k] and self.train_data.num_used_features > 0:
                fmask = self._feature_sample()
                _t0 = time.perf_counter()
                with tel.phase("tree_train", it=self.iter_):
                    new_tree, leaf_id = self.learner.train(
                        grad[k], hess[k], self._bag_mask, fmask)
                # on sampled iterations record tree_train as a sync leg:
                # the host phase's global mean undercounts the sampled
                # wall (iteration 0's compile is always sampled)
                self._sync_sampler.leg("tree_train", _t0, (leaf_id,))
                if tel.enabled and hasattr(self.learner, "take_telemetry"):
                    telem = self.learner.take_telemetry()
                    if telem is not None:
                        telem.copy_to_host_async()
                        tel.device_telem(telem)
            if new_tree.num_leaves > 1:
                should_continue = True
                # score_update here covers the whole post-tree host leg
                # (output renewal + train AND valid score updates) so the
                # attribution table's leg sum tracks the iteration wall on
                # the non-pipelined path too
                _t0 = time.perf_counter()
                with tel.phase("score_update", it=self.iter_):
                    if self.objective is not None:
                        score_np = np.asarray(self.train_score.score[k])
                        self.objective.renew_tree_output(
                            new_tree, score_np[:self.num_data],
                            leaf_id, self._np_bag())
                    new_tree.apply_shrinkage(self.shrinkage_rate)
                    self.train_score.add_by_leaf_id(
                        new_tree.leaf_value[:new_tree.num_leaves], leaf_id, k)
                    for vs in self.valid_scores:
                        vs.add_by_tree(new_tree, k)
                self._sync_sampler.leg("score_update", _t0, ())
                if abs(init_scores[k]) > kEpsilon:
                    new_tree.leaf_value[:new_tree.num_leaves] += init_scores[k]
                    new_tree.shrinkage = 1.0
            else:
                # constant tree for the never-trained / unsplittable case
                if len(self.models) < self.num_tree_per_iteration:
                    if not self.class_need_train[k] and self.objective is not None:
                        output = self.objective.boost_from_score(k)
                    else:
                        output = init_scores[k]
                    new_tree = Tree(2)
                    new_tree.num_leaves = 1
                    new_tree.leaf_value[0] = output
                    self.train_score.add_constant(output, k)
                    for vs in self.valid_scores:
                        vs.add_constant(output, k)
            self.models.append(new_tree)

        if not should_continue:
            import warnings
            warnings.warn("Stopped training because there are no more leaves "
                          "that meet the split requirements")
            if len(self.models) > self.num_tree_per_iteration:
                del self.models[-self.num_tree_per_iteration:]
            return True
        self.iter_ += 1
        return False

    def _boost_from_average(self, class_id: int, update_scorer: bool) -> float:
        """`gbdt.cpp:309-331`."""
        if self._models or self.train_score.has_init_score \
                or self.objective is None:
            return 0.0
        if not (self.cfg.boost_from_average or self.train_data.num_used_features == 0):
            return 0.0
        init_score = self.objective.boost_from_score(class_id)
        if abs(init_score) > kEpsilon:
            if update_scorer:
                self.train_score.add_constant(init_score, class_id)
                for vs in self.valid_scores:
                    vs.add_constant(init_score, class_id)
            return init_score
        return 0.0

    # -- full training loop (`gbdt.cpp:243-261`) -----------------------------

    def train(self, snapshot_freq: int = -1, model_output_path: str = "",
              log_fn: Optional[Callable[[str], None]] = None) -> None:
        log = log_fn or (lambda s: print(f"[LightGBM-TPU] [Info] {s}")
                         if self.cfg.verbosity >= 1 else None)
        start = time.time()
        finished = False
        for it in range(self.cfg.num_iterations):
            if finished:
                break
            finished = self.train_one_iter()
            if not finished:
                finished = self.eval_and_check_early_stopping(log)
            if log:
                log(f"{time.time()-start:.6f} seconds elapsed, finished "
                    f"iteration {it + 1}")
            if snapshot_freq > 0 and (it + 1) % snapshot_freq == 0:
                # atomic write + fingerprint sidecar + keep-last-K
                # retention (cfg.snapshot_keep) in one call
                from ..reliability.resume import save_snapshot
                save_snapshot(self, model_output_path, it + 1, self.cfg)

    # -- eval / early stop (`gbdt.cpp:432-533`) ------------------------------

    def eval_and_check_early_stopping(self, log=None) -> bool:
        msg = self.output_metric(self.iter_, log)
        if msg:
            if log:
                log(f"Early stopping at iteration {self.iter_}, the best "
                    f"iteration round is {self.iter_ - self.cfg.early_stopping_round}")
            drop = self.cfg.early_stopping_round * self.num_tree_per_iteration
            del self.models[-drop:]
            return True
        return False

    def output_metric(self, iter_: int, log=None) -> str:
        cfg = self.cfg
        need_output = (iter_ % cfg.metric_freq) == 0
        ret = ""
        msg_lines: List[str] = []
        if need_output:
            for m in self.training_metrics:
                for name, val in m.eval(self._metric_score(self.train_score),
                                        self.objective):
                    line = f"Iteration:{iter_}, training {name} : {val:g}"
                    if log:
                        log(line)
                    self.eval_history.setdefault("training", {}).setdefault(
                        name, []).append(val)
                    if cfg.early_stopping_round > 0:
                        msg_lines.append(line)
        meet = []
        if need_output or cfg.early_stopping_round > 0:
            for i, metrics in enumerate(self.valid_metrics):
                for j, m in enumerate(metrics):
                    results = m.eval(self._metric_score(self.valid_scores[i]),
                                     self.objective)
                    dname = self.valid_names[i]
                    for name, val in results:
                        line = f"Iteration:{iter_}, valid_{i+1} {name} : {val:g}"
                        if need_output and log:
                            log(line)
                        self.eval_history.setdefault(dname, {}).setdefault(
                            name, []).append(val)
                        if cfg.early_stopping_round > 0:
                            msg_lines.append(line)
                    if not ret and cfg.early_stopping_round > 0:
                        factor = 1.0 if m.is_higher_better else -1.0
                        cur = factor * results[-1][1]
                        if cur > self.best_score[i][j]:
                            self.best_score[i][j] = cur
                            self.best_iter[i][j] = iter_
                            meet.append((i, j))
                        elif iter_ - self.best_iter[i][j] >= cfg.early_stopping_round:
                            ret = self.best_msg[i][j]
        for i, j in meet:
            self.best_msg[i][j] = "\n".join(msg_lines)
        return ret

    def _metric_score(self, updater: ScoreUpdater) -> np.ndarray:
        return updater.np_score()

    # -- telemetry (observability/) ------------------------------------------

    def get_telemetry(self, light: bool = False) -> Dict[str, Any]:
        """The JSON telemetry report (observability/schema.json).

        ``light=True`` skips flushing queued pipelined trees — safe to
        call every iteration (``callback.record_telemetry``) because it
        never forces a device sync; the default flushes so the report
        covers every dispatched tree."""
        tel = self.telemetry
        if not light:
            self._flush_pending()
            tel.flush_device()
        if tel.enabled:
            tel.set_provenance(
                tree_learner=str(self.cfg.tree_learner),
                learner=(type(self.learner).__name__
                         if self.learner is not None else None),
                mesh_shape=(str(dict(self._mesh.shape))
                            if self._mesh is not None else None))
            if self._sync_sampler.every > 0:
                tel.set_distributed(sync_every=self._sync_sampler.every)
        ledger = getattr(self.learner, "_ledger", None)
        gauges = {}
        if self.learner is not None and \
                hasattr(self.learner, "memory_gauges"):
            gauges["wave_working_set"] = self.learner.memory_gauges()
        if self.learner is not None:
            gauges["learner"] = type(self.learner).__name__
            # batched-extras reserve: counters["stall_extras"] is usage
            # against this per-tree cap (learner_wave._stall_extras_cap)
            if hasattr(self.learner, "_extras_cap"):
                gauges["stall_extras_cap"] = int(self.learner._extras_cap)
                gauges["stall_vec_cap"] = int(self.learner._vec_cap)
        return tel.report(ledger=ledger, extra_gauges=gauges, light=light)

    # -- prediction ----------------------------------------------------------

    def predict_raw(self, X: np.ndarray, num_iteration: int = -1) -> np.ndarray:
        X = np.ascontiguousarray(X, dtype=np.float64)
        n = X.shape[0]
        k = self.num_tree_per_iteration
        num_models = self._num_models_for(num_iteration)
        cfg = self.cfg
        # device batch predictor (`predictor.py`): exact bin-space traversal
        # of all trees in one scan.  Trained boosters bin against the
        # training mappers; text-loaded boosters get a synthetic bin schema
        # reconstructed from the model text (thresholds become bounds —
        # `predictor.reconstruct_bin_schema`), so they serve on device too.
        # Trees pending a rebind (refit/continue-training on a NEW dataset)
        # must not take this path until rebound.
        big = num_models > 0 and (n * num_models >= 200_000
                                  or cfg.pred_early_stop)
        pred_data = self.train_data
        if pred_data is None and big:
            pred_data = self._prediction_schema()
        use_device = (pred_data is not None and big
                      and not any(getattr(t, "needs_rebind", False)
                                  for t in self.models[:num_models]))
        if use_device:
            from ..predictor import DevicePredictor
            key = (num_models, self._model_version, cfg.pred_early_stop,
                   cfg.pred_early_stop_freq, cfg.pred_early_stop_margin)
            if self._device_predictor is None \
                    or self._device_predictor[0] != key:
                self._device_predictor = (key, DevicePredictor(
                    self, pred_data, num_iteration,
                    pred_early_stop=cfg.pred_early_stop,
                    pred_early_stop_freq=cfg.pred_early_stop_freq,
                    pred_early_stop_margin=cfg.pred_early_stop_margin))
            out = self._device_predictor[1].predict_raw(X)
            return out.astype(np.float64)
        out = np.zeros((n, k), dtype=np.float64)
        for i in range(num_models):
            out[:, i % k] += self.models[i].predict(X)
        return out[:, 0] if k == 1 else out

    def _prediction_schema(self):
        """Synthetic bin schema for a dataset-less (text-loaded) booster,
        built once and cached; ``None`` when reconstruction isn't possible
        (the host numpy path still serves those)."""
        if self._pred_schema is None:
            from ..predictor import reconstruct_bin_schema
            try:
                self._pred_schema = (reconstruct_bin_schema(self),)
            except Exception as e:  # unexpected model text shapes
                import warnings
                warnings.warn("could not reconstruct a device bin schema "
                              f"from the model text ({e}); predictions use "
                              "the host path")
                self._pred_schema = (None,)
        return self._pred_schema[0]

    def predict(self, X: np.ndarray, num_iteration: int = -1,
                raw_score: bool = False, pred_leaf: bool = False) -> np.ndarray:
        if pred_leaf:
            num_models = self._num_models_for(num_iteration)
            X = np.ascontiguousarray(X, dtype=np.float64)
            return np.stack([self.models[i].predict_leaf_index(X)
                             for i in range(num_models)], axis=1)
        raw = self.predict_raw(X, num_iteration)
        if raw_score or self.objective is None:
            return raw
        return self.objective.convert_output(raw)

    def _num_models_for(self, num_iteration: int) -> int:
        if num_iteration <= 0:
            return len(self.models)
        return min(len(self.models),
                   num_iteration * self.num_tree_per_iteration)

    @property
    def num_iterations_trained(self) -> int:
        return len(self.models) // max(self.num_tree_per_iteration, 1)

    def rollback_one_iter(self) -> None:
        """`gbdt.cpp:414-431` — drop the last iteration's trees and undo their
        score contribution."""
        if self.iter_ <= 0:
            return
        self._model_version += 1
        for k in range(self.num_tree_per_iteration):
            idx = len(self.models) - self.num_tree_per_iteration + k
            tree = self.models[idx]
            tree.apply_shrinkage(-1.0)
            if tree.num_leaves > 1:
                delta = _traverse_tree_binned(self.train_data, tree)
                self.train_score.score = self.train_score.score.at[k].add(delta)
                for vs in self.valid_scores:
                    vs.add_by_tree(tree, k)
            else:
                self.train_score.add_constant(float(tree.leaf_value[0]), k)
                for vs in self.valid_scores:
                    vs.add_constant(float(tree.leaf_value[0]), k)
        del self.models[-self.num_tree_per_iteration:]
        self.iter_ -= 1

    # -- serialization (`gbdt_model_text.cpp:244-341`) -----------------------

    def save_model_to_string(self, start_iteration: int = 0,
                             num_iteration: int = -1) -> str:
        out = [self.name]
        out.append(f"version={K_MODEL_VERSION}")
        out.append(f"num_class={max(self.cfg.num_class, 1)}")
        out.append(f"num_tree_per_iteration={self.num_tree_per_iteration}")
        out.append(f"label_index={self.label_idx}")
        out.append(f"max_feature_idx={self.max_feature_idx}")
        if self.objective is not None:
            out.append(f"objective={self.objective.to_string()}")
        if self.average_output:
            out.append("average_output")
        out.append("feature_names=" + " ".join(self.feature_names))
        out.append("feature_infos=" + " ".join(self.feature_infos))

        num_used = len(self.models)
        total_iter = num_used // max(self.num_tree_per_iteration, 1)
        start_iteration = min(max(start_iteration, 0), total_iter)
        if num_iteration > 0:
            num_used = min((start_iteration + num_iteration)
                           * self.num_tree_per_iteration, num_used)
        start_model = start_iteration * self.num_tree_per_iteration
        tree_strs = []
        for i in range(start_model, num_used):
            s = f"Tree={i - start_model}\n" + self.models[i].to_string() + "\n"
            tree_strs.append(s)
        out.append("tree_sizes=" + " ".join(str(len(s)) for s in tree_strs))
        out.append("")
        body = "\n".join(out) + "\n" + "".join(tree_strs)
        body += "end of trees\n"
        imps = self.feature_importance("split")
        pairs = [(int(v), self.feature_names[i]) for i, v in enumerate(imps) if v > 0]
        pairs.sort(key=lambda p: -p[0])
        body += "\nfeature importances:\n"
        for v, name in pairs:
            body += f"{name}={v}\n"
        # pandas category mapping, the python layer's final model line
        # (`basic.py:2233` _dump_pandas_categorical)
        import json as _json
        body += "\npandas_categorical:%s\n" % _json.dumps(
            self.pandas_categorical, default=str)
        return body

    def save_model_to_file(self, filename: str, start_iteration: int = 0,
                           num_iteration: int = -1) -> None:
        """Atomic write: tempfile in the target directory + ``os.replace``,
        so a preemption mid-write (snapshot_iter_* checkpoints especially)
        never leaves a truncated model behind."""
        import os
        import tempfile

        s = self.save_model_to_string(start_iteration, num_iteration)
        d = os.path.dirname(os.path.abspath(filename))
        fd, tmp = tempfile.mkstemp(
            prefix=os.path.basename(filename) + ".", suffix=".tmp", dir=d)
        try:
            with os.fdopen(fd, "w") as fh:
                fh.write(s)
            os.replace(tmp, filename)
        except BaseException:
            try:
                os.unlink(tmp)
            except OSError:
                pass
            raise

    # -- JSON dump (`gbdt_model_text.cpp:15-60` DumpModel) -------------------

    def dump_model(self, start_iteration: int = 0, num_iteration: int = -1
                   ) -> Dict[str, Any]:
        """Model as a JSON-able dict, the reference ``DumpModel`` schema."""
        k = max(self.num_tree_per_iteration, 1)
        models = self.models
        total_iteration = len(models) // k
        start_iteration = min(max(start_iteration, 0), total_iteration)
        num_used = len(models)
        if num_iteration > 0:
            num_used = min((start_iteration + num_iteration) * k, num_used)
        out: Dict[str, Any] = {
            "name": "tree",
            "version": K_MODEL_VERSION,
            "num_class": max(self.cfg.num_class, 1),
            "num_tree_per_iteration": self.num_tree_per_iteration,
            "label_index": self.label_idx,
            "max_feature_idx": self.max_feature_idx,
            "average_output": self.average_output,
        }
        if self.objective is not None:
            out["objective"] = self.objective.to_string()
        out["feature_names"] = list(self.feature_names)
        out["tree_info"] = [
            dict(tree_index=i - start_iteration * k,
                 **models[i].to_json())
            for i in range(start_iteration * k, num_used)]
        return out

    # -- refit (`gbdt.cpp` RefitTree + `serial_tree_learner.cpp`
    #    FitByExistingTree) --------------------------------------------------

    def refit_leaf_preds(self, leaf_preds: np.ndarray,
                         decay_rate: float = 0.9) -> None:
        """Refit every tree's leaf values on this booster's CURRENT train
        data: per iteration, gradients at the running score, per-leaf
        grad/hess sums, ``decay·old + (1-decay)·new·shrinkage``."""
        models = self.models  # flush pending
        self._model_version += 1
        k = max(self.num_tree_per_iteration, 1)
        n = self.num_data
        assert leaf_preds.shape == (n, len(models)), \
            (leaf_preds.shape, n, len(models))
        from ..ops.split import calculate_leaf_output
        cfg = self.cfg
        # zero the running score — refit replays boosting from scratch
        self.train_score.score = jnp.zeros_like(self.train_score.score)
        for it in range(len(models) // k):
            grad, hess = self._compute_gradients()
            g_np = np.asarray(grad)[:, :n]
            h_np = np.asarray(hess)[:, :n]
            for tid in range(k):
                mi = it * k + tid
                tree = models[mi]
                lp = leaf_preds[:, mi].astype(np.int64)
                nl = tree.num_leaves
                sum_g = np.bincount(lp, weights=g_np[tid], minlength=nl)
                sum_h = np.bincount(lp, weights=h_np[tid],
                                    minlength=nl) + kEpsilon
                new_out = np.asarray(calculate_leaf_output(
                    jnp.asarray(sum_g), jnp.asarray(sum_h),
                    float(cfg.lambda_l1), float(cfg.lambda_l2),
                    float(cfg.max_delta_step)))
                old = tree.leaf_value[:nl]
                tree.leaf_value[:nl] = (decay_rate * old
                                        + (1.0 - decay_rate)
                                        * new_out * tree.shrinkage)
                # AddScore with the new leaf values over the refit data
                lv = jnp.asarray(tree.leaf_value[:nl].astype(np.float32))
                pad = self.train_data.num_data_padded - n
                lp_pad = jnp.asarray(np.pad(lp, (0, pad)))
                self.train_score.score = self.train_score.score.at[tid].add(
                    jnp.where(jnp.arange(len(lp_pad)) < n, lv[lp_pad], 0.0))
                if hasattr(tree, "_traverse_pack"):
                    del tree._traverse_pack

    def load_model_from_string(self, s: str) -> "GBDT":
        """`gbdt_model_text.cpp:343-440`."""
        for line in s.rsplit("\n", 3)[1:]:
            if line.startswith("pandas_categorical:"):
                import json as _json
                try:
                    self.pandas_categorical = _json.loads(
                        line[len("pandas_categorical:"):])
                except ValueError:
                    self.pandas_categorical = None
        lines, trees_part = s.split("tree_sizes=", 1)
        header: Dict[str, str] = {}
        for line in lines.strip().split("\n"):
            if "=" in line:
                k, v = line.split("=", 1)
                header[k] = v
            elif line.strip() == "average_output":
                self.average_output = True
        self.num_tree_per_iteration = int(header.get("num_tree_per_iteration", 1))
        self.cfg.num_class = int(header.get("num_class", 1))
        self.label_idx = int(header.get("label_index", 0))
        self.max_feature_idx = int(header.get("max_feature_idx", 0))
        self.feature_names = header.get("feature_names", "").split()
        self.feature_infos = header.get("feature_infos", "").split()
        if "objective" in header and self.objective is None:
            obj_str = header["objective"]
            self.cfg.objective = _objective_from_string(obj_str, self.cfg)
            self.objective = create_objective(self.cfg)
        self.models = []
        body = trees_part.split("\n", 1)[1]
        for block in body.split("Tree=")[1:]:
            tree_txt = block.split("\n\n")[0]
            tree_txt = tree_txt.split("end of trees")[0]
            tree_txt = tree_txt.split("\n", 1)[1]  # drop the tree index line
            self.models.append(Tree.from_string(tree_txt))
        self.iter_ = len(self.models) // max(self.num_tree_per_iteration, 1)
        return self

    # -- importances (`boosting.h:224`, `gbdt.cpp` FeatureImportance) --------

    def feature_importance(self, importance_type: str = "split",
                           num_iteration: int = -1) -> np.ndarray:
        num_models = self._num_models_for(num_iteration)
        out = np.zeros(self.max_feature_idx + 1, dtype=np.float64)
        for i in range(num_models):
            t = self.models[i]
            for nd in range(t.num_leaves - 1):
                if importance_type == "split":
                    out[t.split_feature[nd]] += 1.0
                else:
                    out[t.split_feature[nd]] += max(t.split_gain[nd], 0.0)
        return out


def _feature_infos(data: _ConstructedDataset) -> List[str]:
    """``feature_infos`` strings: [min:max] per feature or categorical list
    (`dataset.cpp` SaveModelToString feature info)."""
    out = ["none"] * data.num_total_features
    for k, m in enumerate(data.bin_mappers):
        j = int(data.used_feature_map[k])
        if m.bin_type == 1:
            out[j] = ":".join(str(c) for c in m.bin_2_categorical)
        else:
            out[j] = f"[{m.min_val:g}:{m.max_val:g}]"
    return out


def _objective_from_string(s: str, cfg: Config) -> str:
    parts = s.split()
    name = parts[0]
    for tok in parts[1:]:
        if ":" in tok:
            k, v = tok.split(":", 1)
            try:
                setattr(cfg, k, type(getattr(cfg, k, 0.0))(v))
            except Exception:
                pass
    return {"xentropy": "cross_entropy", "xentlambda": "cross_entropy_lambda"
            }.get(name, name)
