"""Parallel tree learning via declarative sharding.

TPU-native re-design of the reference's three parallel learners:

  * data-parallel (`src/treelearner/data_parallel_tree_learner.cpp:49-254`):
    each machine owns a row shard, builds local histograms for all features,
    and the histograms are summed with ``ReduceScatter`` +
    ``HistogramBinEntry::SumReducer`` (`include/LightGBM/bin.h:40-56`), then
    the best split is agreed with an Allreduce of max-gain SplitInfos
    (`parallel_tree_learner.h:186-209`).
  * feature-parallel (`feature_parallel_tree_learner.cpp:29-73`): each
    machine owns a feature shard and all the data; only the tiny best-split
    message crosses the wire.
  * voting-parallel (`voting_parallel_tree_learner.cpp:166-345`): data
    parallel with top-k feature voting to cut communication.

Here none of those collectives are written by hand.  The binned matrix and
row-aligned vectors carry `jax.sharding.NamedSharding` annotations and the
SAME jitted tree-build step compiles under GSPMD: the one-hot histogram
contraction over a row-sharded axis lowers to partial sums plus an
all-reduce over ICI (the exact rewiring SURVEY §2.6 calls for at the
``Network::Init`` external-function seam, `network.h:96`), the per-feature
argmax over a feature-sharded axis lowers to an all-gather of per-shard
bests.  ``Network`` as a class does not exist — the mesh is the network.
"""

from __future__ import annotations

from typing import Optional

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .sharding import (AXIS_DATA, AXIS_FEATURE, default_mesh_shape_2d,
                       feature_axis, make_mesh, row_axis, rules_for_mode)


def _route_log(cfg, msg: str) -> None:
    """Learner-selection telemetry, mirroring the serial factory's
    (`learner_compact.py` create_tree_learner): a user with 8 chips and an
    off-by-one row count must be TOLD they got the slow masked path."""
    if int(getattr(cfg, "verbosity", 1)) >= 1:
        print(f"[lightgbm_tpu] {msg}")


def _fast_gate_reason(data, mesh_size: int) -> Optional[str]:
    """Why the sharded compact/wave path cannot run (None = eligible)."""
    if data.max_num_bin > 256:
        return f"max_num_bin={data.max_num_bin} > 256"
    if data.num_data_padded % mesh_size:
        return (f"padded row count {data.num_data_padded} not divisible by "
                f"mesh size {mesh_size}")
    if data.bins.shape[0] % mesh_size:
        return (f"padded feature count {data.bins.shape[0]} not divisible "
                f"by mesh size {mesh_size}")
    return None


def apply_parallel_sharding(gbdt, mesh: Mesh, mode: str) -> None:
    """Re-place a GBDT's device arrays for a parallel mode.  Subsequent jitted
    steps compile under GSPMD with collectives over the mesh."""
    from ..learner import TPUTreeLearner

    # pipelined iterations queued before the swap hold compact-format records
    # — materialize them with the learner that produced them
    if hasattr(gbdt, "_flush_pending"):
        gbdt._flush_pending()
    learner = gbdt.learner
    # forced splits ride the sharded COMPACT learners (the wave learners
    # don't carry the forced phase, mirroring the serial factory's routing)
    forced = getattr(learner, "_forced", None)
    mesh_size = max(int(np.prod(mesh.devices.shape)), 1)
    if mode == "data_feature":
        from .wave2d_sharded import (ShardedWave2DLearner,
                                     wave2d_ineligible_reason)
        if len(mesh.axis_names) < 2:
            # a flat mesh was passed: factor it into (data, feature)
            mesh = make_mesh(shape=default_mesh_shape_2d(mesh_size),
                             devices=list(mesh.devices.reshape(-1)),
                             axis_names=(AXIS_DATA, AXIS_FEATURE))
        reason = ("forced splits ride the sequential sharded learner"
                  if forced else
                  wave2d_ineligible_reason(learner.cfg, learner.data, mesh))
        if reason is None:
            shp = dict(zip(mesh.axis_names, mesh.devices.shape))
            _route_log(learner.cfg,
                       f"tree_learner=data_feature: using "
                       f"ShardedWave2DLearner over a "
                       f"{shp[AXIS_DATA]}x{shp[AXIS_FEATURE]} "
                       f"(data x feature) mesh")
            gbdt.learner = ShardedWave2DLearner(learner.cfg, learner.data,
                                                mesh)
            _place_row_arrays(gbdt, mesh, mode)
            gbdt._mesh = mesh
            gbdt._parallel_mode = mode
            return
        _route_log(learner.cfg,
                   f"tree_learner=data_feature: 2D hybrid ineligible "
                   f"({reason}); falling back to tree_learner=data over a "
                   f"flat {mesh_size}-device mesh")
        apply_parallel_sharding(
            gbdt, make_mesh(devices=list(mesh.devices.reshape(-1))), "data")
        return
    fast_reason = _fast_gate_reason(learner.data, mesh_size) \
        if mode in ("data", "voting") else None
    if mode in ("data", "voting") and fast_reason is None:
        # the real distributed path: per-shard compact learner with
        # reduce-scattered histograms; voting adds PV-Tree feature election
        # (`compact_sharded.py`)
        from .compact_sharded import (ShardedCompactLearner,
                                      ShardedVotingLearner)
        from .wave_sharded import wave_sharded_eligible
        wave_ok = not forced and wave_sharded_eligible(
            learner.cfg, learner.data, mesh_size)
        if mode == "voting":
            from .wave_sharded import ShardedVotingWaveLearner
            cls = ShardedVotingWaveLearner if wave_ok \
                else ShardedVotingLearner
        else:
            # data-parallel rides the frontier-wave learner where eligible
            # (the reference templates its parallel learners over its
            # fastest serial learner, `data_parallel_tree_learner.cpp:257`)
            from .wave_sharded import ShardedWaveLearner
            cls = ShardedWaveLearner if wave_ok else ShardedCompactLearner
        if not wave_ok:
            why = "forced splits ride the sequential sharded learner" \
                if forced else "shape/byte gates, see wave_sharded_eligible"
            _route_log(learner.cfg,
                       f"tree_learner={mode}: wave-sharded learner "
                       f"ineligible ({why}); using the sequential "
                       f"{cls.__name__}")
        else:
            _route_log(learner.cfg,
                       f"tree_learner={mode}: using {cls.__name__} over "
                       f"{mesh_size} devices")
        gbdt.learner = cls(learner.cfg, learner.data, mesh)
        if forced:
            gbdt.learner.set_forced_splits(forced)
        _place_row_arrays(gbdt, mesh, mode)
        gbdt._mesh = mesh
        gbdt._parallel_mode = mode
        return
    if mode == "feature" and learner.data.max_num_bin <= 256:
        from ..learner_wave import wave_budget_reason
        from .feature_sharded import (FeatureShardedCompactLearner,
                                      FeatureShardedWaveLearner,
                                      feature_sharded_eligible)
        if feature_sharded_eligible(learner.cfg, learner.data, mesh_size):
            # rows are REPLICATED in feature-parallel, so the wave variant
            # must pass the serial wave gates at the FULL row count and
            # width (wide datasets use the feature-sharded compact learner
            # — its scans are feature-sliced either way)
            wave_ok = (not forced
                       and learner.cfg.tpu_learner in ("auto", "wave")
                       and wave_budget_reason(
                           learner.cfg, int(learner.data.num_data_padded),
                           learner.data.bins.shape[0],
                           int(learner.data.max_num_bin)) is None)
            cls = FeatureShardedWaveLearner if wave_ok \
                else FeatureShardedCompactLearner
            _route_log(learner.cfg,
                       f"tree_learner=feature: using {cls.__name__} over "
                       f"{mesh_size} devices")
            gbdt.learner = cls(learner.cfg, learner.data, mesh)
            if forced:
                gbdt.learner.set_forced_splits(forced)
            gbdt._mesh = mesh
            gbdt._parallel_mode = mode
            return
    # every fast path refused — name the failed gate before draping GSPMD
    # over the masked learner (round-2-era performance)
    if mode in ("data", "voting"):
        _route_log(learner.cfg,
                   f"tree_learner={mode}: sharded compact/wave path "
                   f"ineligible ({fast_reason}); falling back to the "
                   f"masked GSPMD learner")
    elif mode == "feature":
        why = (f"max_num_bin={learner.data.max_num_bin} > 256"
               if learner.data.max_num_bin > 256
               else "feature_sharded_eligible gates failed")
        _route_log(learner.cfg,
                   f"tree_learner=feature: feature-sharded path ineligible "
                   f"({why}); falling back to the masked GSPMD learner")
    if type(learner) is not TPUTreeLearner:
        # feature-parallel / >256-bin fallbacks drape GSPMD over the masked
        # learner — the compact learner's packed-bin cache and global-axis
        # sort would silently ignore the sharding mutations below
        learner = TPUTreeLearner(learner.cfg, learner.data,
                                 learner.hist_backend)
        if forced:
            learner.set_forced_splits(forced)
        gbdt.learner = learner
    ax_r, ax_f = row_axis(mesh), feature_axis(mesh)
    if mode in ("data", "voting"):
        bins_spec = P(None, ax_r)      # (F, N): shard rows
        row_spec = P(ax_r)
    elif mode == "feature":
        bins_spec = P(ax_f, None)      # shard features, replicate rows
        row_spec = P()
    else:
        raise ValueError(f"unknown parallel mode: {mode}")

    put = lambda arr, spec: jax.device_put(arr, NamedSharding(mesh, spec))
    # the Pallas kernel has no GSPMD partitioning rule; under a sharded mesh
    # the XLA one-hot path is used instead — it auto-partitions and lowers
    # the row reduction to an all-reduce over ICI.  (A shard_map-wrapped
    # pallas-per-shard + psum path is the planned upgrade.)
    learner.hist_backend = "onehot"
    learner.bins = put(learner.bins, bins_spec)
    learner.data._device_bins = learner.bins
    # per-feature metadata is replicated
    learner.f_num_bin = put(learner.f_num_bin, P())
    learner.f_missing = put(learner.f_missing, P())
    learner.f_default_bin = put(learner.f_default_bin, P())
    # row-aligned vectors
    gbdt._valid_rows = put(gbdt._valid_rows, row_spec)
    gbdt._bag_mask = put(gbdt._bag_mask, row_spec)
    score_spec = P(None, ax_r) if mode in ("data", "voting") else P()
    gbdt.train_score.score = put(gbdt.train_score.score, score_spec)
    # objective label arrays follow the rows
    obj = gbdt.objective
    if obj is not None:
        for name in obj.ROW_ARRAYS:
            arr = getattr(obj, name, None)
            if arr is not None and hasattr(arr, "shape") and arr.ndim >= 1:
                spec = row_spec if arr.ndim == 1 else P(None, ax_r) \
                    if mode in ("data", "voting") else P()
                try:
                    setattr(obj, name, put(arr, spec))
                except Exception as e:
                    import warnings
                    warnings.warn(f"could not shard objective array "
                                  f"{name!r} over the mesh: {e}")
    gbdt._mesh = mesh
    gbdt._parallel_mode = mode


def _place_row_arrays(gbdt, mesh: Mesh, mode: str) -> None:
    """Shard the boosting loop's row-aligned arrays (score, bagging mask,
    objective label arrays) over the mesh — rule-driven
    (`parallel/sharding.py`), so the same call covers 1-D and 2-D modes."""
    rules = rules_for_mode(mode, mesh)
    gbdt._valid_rows = rules.place("valid_rows", gbdt._valid_rows)
    gbdt._bag_mask = rules.place("bag_mask", gbdt._bag_mask)
    gbdt.train_score.score = rules.place("score", gbdt.train_score.score)
    obj = gbdt.objective
    if obj is not None:
        for name in obj.ROW_ARRAYS:
            arr = getattr(obj, name, None)
            if arr is not None and hasattr(arr, "shape") and arr.ndim >= 1:
                try:
                    setattr(obj, name, rules.place(name, arr))
                except Exception as e:
                    import warnings
                    warnings.warn(f"could not shard objective array "
                                  f"{name!r} over the mesh: {e}")


def make_data_parallel(gbdt, num_devices: Optional[int] = None) -> Mesh:
    """`tree_learner=data` over the local mesh."""
    mesh = make_mesh(num_devices)
    apply_parallel_sharding(gbdt, mesh, "data")
    return mesh


def make_feature_parallel(gbdt, num_devices: Optional[int] = None) -> Mesh:
    """`tree_learner=feature` over the local mesh."""
    mesh = make_mesh(num_devices)
    apply_parallel_sharding(gbdt, mesh, "feature")
    return mesh


def make_hybrid_parallel(gbdt, shape=None) -> Mesh:
    """`tree_learner=data_feature` over a 2-D (data, feature) mesh;
    ``shape=(2, 4)``-style, auto-factored over the local devices when
    omitted."""
    if shape is None:
        shape = default_mesh_shape_2d(len(jax.devices()))
    mesh = make_mesh(shape=shape, axis_names=(AXIS_DATA, AXIS_FEATURE))
    apply_parallel_sharding(gbdt, mesh, "data_feature")
    return mesh
