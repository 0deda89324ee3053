"""Binned training dataset resident in HBM.

TPU-native re-design of the reference data layer (`include/LightGBM/dataset.h:278-627`,
`src/io/dataset.cpp`, `src/io/dataset_loader.cpp`).  Key departures, per the
tpu-first architecture:

  * The binned matrix is ONE dense ``(num_used_features, num_rows_padded)``
    uint8/uint16 array in HBM — there is no dense/sparse/4-bit bin zoo
    (`src/io/dense_bin.hpp`, `sparse_bin.hpp`, `ordered_sparse_bin.hpp`);
    after binning, "sparse" merely means a popular default bin and TPUs want
    dense loads feeding the MXU.
  * Rows are padded to a multiple of the row block so every kernel sees static
    shapes; padded rows carry zero weight everywhere.
  * Feature bundling (EFB, `src/io/dataset.cpp:67-213`) is host-side
    preprocessing and is handled as a feature-count reducer (future work keyed
    behind ``enable_bundle``); trivial features are dropped exactly like the
    reference (``BinMapper::is_trivial``).

Metadata (labels / weights / query boundaries / init scores) mirrors
``Metadata`` (`include/LightGBM/dataset.h:36-245`).
"""

from __future__ import annotations

import math
import os
import time
from typing import Dict, List, Optional, Sequence, Tuple, Union

import numpy as np

from .binning import (BIN_CATEGORICAL, BIN_NUMERICAL, MISSING_NAN, MISSING_NONE,
                      MISSING_ZERO, BinMapper)
from .config import Config, resolve_aliases

_ArrayLike = Union[np.ndarray, Sequence[float], None]


def _round_up(x: int, mult: int) -> int:
    return ((x + mult - 1) // mult) * mult


class Metadata:
    """Labels, weights, query boundaries, init scores
    (reference `dataset.h:36-245`, `src/io/metadata.cpp`)."""

    def __init__(self, num_data: int):
        self.num_data = num_data
        self.label = np.zeros(num_data, dtype=np.float32)
        self.weights: Optional[np.ndarray] = None
        self.query_boundaries: Optional[np.ndarray] = None
        self.init_score: Optional[np.ndarray] = None

    def set_label(self, label: _ArrayLike) -> None:
        arr = np.asarray(label, dtype=np.float32).reshape(-1)
        if len(arr) != self.num_data:
            raise ValueError(f"Length of label ({len(arr)}) != num_data ({self.num_data})")
        self.label = arr

    def set_weights(self, weights: _ArrayLike) -> None:
        if weights is None:
            self.weights = None
            return
        arr = np.asarray(weights, dtype=np.float32).reshape(-1)
        if len(arr) != self.num_data:
            raise ValueError(f"Length of weights ({len(arr)}) != num_data ({self.num_data})")
        self.weights = arr

    def set_group(self, group: _ArrayLike) -> None:
        """Accepts per-query sizes (like the reference's query file) and stores
        boundaries (`metadata.cpp` ``SetQuery``)."""
        if group is None:
            self.query_boundaries = None
            return
        arr = np.asarray(group, dtype=np.int64).reshape(-1)
        bounds = np.concatenate([[0], np.cumsum(arr)])
        if bounds[-1] != self.num_data:
            raise ValueError(f"Sum of group sizes ({bounds[-1]}) != num_data ({self.num_data})")
        self.query_boundaries = bounds.astype(np.int32)

    def set_init_score(self, init_score: _ArrayLike) -> None:
        if init_score is None:
            self.init_score = None
            return
        self.init_score = np.asarray(init_score, dtype=np.float64).reshape(-1)

    @property
    def num_queries(self) -> int:
        return 0 if self.query_boundaries is None else len(self.query_boundaries) - 1

    def subset(self, idx: np.ndarray) -> "Metadata":
        """Row-subset (`metadata.cpp` Init(metadata, used_indices)); query
        boundaries are rebuilt only when the subset keeps whole queries in
        order."""
        out = Metadata(len(idx))
        out.label = self.label[idx]
        if self.weights is not None:
            out.weights = self.weights[idx]
        if self.init_score is not None:
            k = len(self.init_score) // max(self.num_data, 1)
            out.init_score = self.init_score.reshape(
                k, self.num_data)[:, idx].reshape(-1)
        if self.query_boundaries is not None:
            qid = np.searchsorted(self.query_boundaries, idx, "right") - 1
            if (np.diff(qid) >= 0).all():
                _, sizes = np.unique(qid, return_counts=True)
                out.set_group(sizes)
            else:
                raise ValueError("subset of a ranking dataset must keep "
                                 "query groups contiguous")
        return out


def recode_pandas(df, cat_cols, stored) -> np.ndarray:
    """DataFrame → float64 matrix with ``category`` columns coded through
    the ``stored`` category lists (positional pairing; values outside a
    stored list → NaN).  Shared by training-time ``_data_from_pandas`` and
    predict-time re-coding so the semantics cannot drift."""
    cols = []
    ci = 0
    for j in range(df.shape[1]):
        s = df.iloc[:, j]
        if j in cat_cols:
            s = s.cat.set_categories(stored[ci])
            ci += 1
            codes = s.cat.codes.to_numpy().astype(np.float64)
            codes[codes < 0] = np.nan
            cols.append(codes)
        else:
            cols.append(np.asarray(s, dtype=np.float64))
    return np.column_stack(cols)


class Dataset:
    """User-facing dataset (mirrors `python-package/lightgbm/basic.py:655-1575`
    ``Dataset`` semantics: lazy construction, reference-linked validation sets).
    """

    def __init__(self, data, label=None, reference: Optional["Dataset"] = None,
                 weight=None, group=None, init_score=None, feature_name="auto",
                 categorical_feature="auto", params: Optional[Dict] = None,
                 free_raw_data: bool = False):
        self.params = dict(params or {})
        self._raw_data = data
        self._label = label
        self._weight = weight
        self._group = group
        self._init_score = init_score
        self.reference = reference
        self.feature_name = feature_name
        self.categorical_feature = categorical_feature
        self.free_raw_data = free_raw_data
        self._constructed: Optional[_ConstructedDataset] = None
        self.used_indices: Optional[np.ndarray] = None
        # category-dtype mapping recorded by `_data_from_pandas`
        # (`basic.py:262-304`): list of per-column category lists, stored in
        # the model so predict-time DataFrames re-apply the same code space
        self.pandas_categorical: Optional[List[list]] = None
        self._pandas_cat_cols: List[int] = []

    # -- lazy construction (basic.py:970 ``construct``) ---------------------

    def construct(self) -> "Dataset":
        if self._constructed is None:
            cfg = Config.from_params(self.params)
            if cfg.elastic and not (
                    isinstance(self._raw_data, str) and cfg.two_round
                    and self.reference is None
                    and not _ConstructedDataset.is_binary_file(
                        self._raw_data)):
                import warnings
                warnings.warn(
                    "elastic=true but this Dataset is not a two_round "
                    "file source: in-memory (and binary/reference) "
                    "Datasets CANNOT re-deal rows after a membership "
                    "shrink — whatever rows this process holds is all "
                    "it will ever have. Only from_stream sources "
                    "(two_round=true with a file path) survive elastic "
                    "recovery; this run will NOT be elastic-safe.",
                    RuntimeWarning, stacklevel=3)
            if isinstance(self._raw_data, str) and \
                    _ConstructedDataset.is_binary_file(self._raw_data):
                self._constructed = _ConstructedDataset.load_binary(
                    self._raw_data, cfg)
                # user-supplied fields override the cached metadata, same as
                # the raw-data path below
                if self._label is not None:
                    self._constructed.metadata.set_label(self._label)
                if self._weight is not None:
                    self._constructed.metadata.set_weights(self._weight)
                if self._group is not None:
                    self._constructed.metadata.set_group(self._group)
                if self._init_score is not None:
                    self._constructed.metadata.set_init_score(self._init_score)
                return self
            if isinstance(self._raw_data, str) and cfg.two_round \
                    and self.reference is None:
                # out-of-core two-pass streaming load (`two_round=true`,
                # the reference's use_two_round_loading): the full float64
                # matrix is never materialized — see
                # `_ConstructedDataset.from_stream`
                from .io.parser import scan_data_file
                info = scan_data_file(self._raw_data, self.params)
                shape_shim = type("_Shape", (), {
                    "shape": (info.num_rows, info.num_features)})
                from .parallel import multihost
                if cfg.elastic and multihost.is_initialized():
                    # elastic re-deal: rank / num_machines come from the
                    # CURRENT membership epoch's live world, not config
                    from .elastic.redeal import construct_elastic
                    self._constructed = construct_elastic(
                        self._raw_data, self.params, cfg,
                        categorical=self._resolve_categorical(shape_shim),
                        feature_names=self._resolve_feature_names(
                            shape_shim),
                        info=info)
                else:
                    self._constructed = _ConstructedDataset.from_stream(
                        self._raw_data, self.params, cfg,
                        categorical=self._resolve_categorical(shape_shim),
                        feature_names=self._resolve_feature_names(
                            shape_shim),
                        info=info)
                if self._label is not None:
                    self._constructed.metadata.set_label(self._label)
                if self._weight is not None:
                    self._constructed.metadata.set_weights(self._weight)
                if self._group is not None:
                    self._constructed.metadata.set_group(self._group)
                if self._init_score is not None:
                    self._constructed.metadata.set_init_score(self._init_score)
                return self
            if self.reference is not None:
                # construct the reference FIRST: _data_from_pandas needs its
                # recorded category lists to code this frame consistently
                self.reference.construct()
            data = self._load_raw(self._raw_data)
            if self.reference is not None:
                ref = self.reference._constructed
                self._constructed = _ConstructedDataset.from_reference(
                    data, ref, cfg)
            else:
                cat = self._resolve_categorical(data)
                self._constructed = _ConstructedDataset.from_matrix(
                    data, cfg, categorical=cat,
                    feature_names=self._resolve_feature_names(data))
            if self._label is not None:
                self._constructed.metadata.set_label(self._label)
            if self._weight is not None:
                self._constructed.metadata.set_weights(self._weight)
            if self._group is not None:
                self._constructed.metadata.set_group(self._group)
            if self._init_score is not None:
                self._constructed.metadata.set_init_score(self._init_score)
            if self.free_raw_data:
                self._raw_data = None
        return self

    def _load_raw(self, data) -> np.ndarray:
        if isinstance(data, str):
            from .io.parser import load_data_file
            mat, label, weight, group = load_data_file(data, self.params)
            if self._label is None and label is not None:
                self._label = label
            if self._weight is None and weight is not None:
                self._weight = weight
            if self._group is None and group is not None:
                self._group = group
            return mat
        if hasattr(data, "toarray"):  # scipy sparse
            return np.asarray(data.toarray(), dtype=np.float64)
        if hasattr(data, "dtypes") and hasattr(data, "columns") \
                and not isinstance(data, np.ndarray):  # pandas DataFrame
            return self._data_from_pandas(data)
        if hasattr(data, "values") and not isinstance(data, np.ndarray):
            return np.asarray(data.values, dtype=np.float64)
        return _float_matrix(data)

    def _data_from_pandas(self, df) -> np.ndarray:
        """DataFrame → float64 matrix with the reference's category-dtype
        semantics (`python-package/lightgbm/basic.py:262-304`
        ``_data_from_pandas``): ``category`` columns convert to their codes
        (-1/unseen → NaN); the per-column category lists are recorded on
        first use (training) or re-applied from the reference dataset
        (valid sets), so the code space matches across datasets and
        save/load."""
        cat_cols = [j for j, c in enumerate(df.columns)
                    if str(df.dtypes.iloc[j]) == "category"]
        if not cat_cols:
            return np.asarray(df.values, dtype=np.float64)
        stored = None
        ref = self.reference
        if ref is not None:
            stored = getattr(ref, "pandas_categorical", None)
        if stored is None:
            stored = [df.iloc[:, j].cat.categories.tolist()
                      for j in cat_cols]
        if len(stored) != len(cat_cols):
            raise ValueError(
                "train and valid dataset categorical_feature do not match "
                f"({len(stored)} recorded category columns vs "
                f"{len(cat_cols)} in this DataFrame)")
        self.pandas_categorical = stored
        self._pandas_cat_cols = list(cat_cols)
        return recode_pandas(df, cat_cols, stored)

    def _resolve_feature_names(self, data) -> List[str]:
        if isinstance(self.feature_name, (list, tuple)):
            return list(self.feature_name)
        raw = self._raw_data
        if hasattr(raw, "columns"):
            return [str(c) for c in raw.columns]
        return [f"Column_{i}" for i in range(data.shape[1])]

    def _resolve_categorical(self, data) -> List[int]:
        cf = self.categorical_feature
        if cf == "auto" or cf is None or cf == "":
            # 'auto' = pandas category-dtype columns (`basic.py:262-304`),
            # then the config parameter (`categorical_feature=0,1,2` or
            # `name:c1,c2` — `config.h:438-446` / `config.cpp` parsing)
            if self._pandas_cat_cols:
                return sorted(self._pandas_cat_cols)
            cf = Config.from_params(self.params).categorical_feature
            if not cf:
                return []
        if isinstance(cf, str):
            if cf.startswith("name:"):
                cf = [c.strip() for c in cf[5:].split(",") if c.strip()]
            else:
                cf = [int(c) for c in cf.split(",") if c.strip()]
        names = self._resolve_feature_names(data)
        out = []
        for c in cf:
            if isinstance(c, str):
                out.append(names.index(c))
            else:
                out.append(int(c))
        return sorted(out)

    # convenience accessors matching the reference python API
    def set_label(self, label):
        self._label = label
        if self._constructed:
            self._constructed.metadata.set_label(label)
        return self

    def set_weight(self, weight):
        self._weight = weight
        if self._constructed:
            self._constructed.metadata.set_weights(weight)
        return self

    def set_group(self, group):
        self._group = group
        if self._constructed:
            self._constructed.metadata.set_group(group)
        return self

    def set_init_score(self, init_score):
        self._init_score = init_score
        if self._constructed:
            self._constructed.metadata.set_init_score(init_score)
        return self

    def get_label(self):
        if self._constructed is not None:
            return self._constructed.metadata.label
        return self._label

    def get_weight(self):
        if self._constructed is not None:
            return self._constructed.metadata.weights
        return self._weight

    def get_group(self):
        if self._constructed is not None and self._constructed.metadata.query_boundaries is not None:
            return np.diff(self._constructed.metadata.query_boundaries)
        return self._group

    def get_init_score(self):
        if self._constructed is not None:
            return self._constructed.metadata.init_score
        return self._init_score

    def num_data(self) -> int:
        return self.construct()._constructed.num_data

    def num_feature(self) -> int:
        return self.construct()._constructed.num_total_features

    def create_valid(self, data, label=None, weight=None, group=None,
                     init_score=None, params=None) -> "Dataset":
        return Dataset(data, label=label, reference=self, weight=weight,
                       group=group, init_score=init_score,
                       params=params or self.params)

    @property
    def constructed(self) -> "_ConstructedDataset":
        return self.construct()._constructed

    # -- binary cache (`basic.py:1078` save_binary /
    #    `dataset_loader.cpp:266` LoadFromBinFile).  The format is our own
    #    (npz of bins + mappers + metadata) — binning once and reloading the
    #    cache skips the whole find-bin/bin-all pass. -----------------------

    def save_binary(self, filename: str) -> "Dataset":
        self.construct()._constructed.save_binary(filename)
        return self

    @classmethod
    def _from_constructed(cls, constructed: "_ConstructedDataset",
                          params: Optional[Dict] = None) -> "Dataset":
        ds = cls(None, params=params)
        ds._constructed = constructed
        return ds

    # -- subset / feature concat (`basic.py:1053` subset,
    #    `basic.py:1121` add_features_from) --------------------------------

    def subset(self, used_indices, params: Optional[Dict] = None) -> "Dataset":
        """Row-subset sharing this dataset's bin mappers (no re-binning)."""
        con = self.construct()._constructed
        idx = np.asarray(used_indices, dtype=np.int64)
        sub = _ConstructedDataset()
        sub.num_data = len(idx)
        sub.num_total_features = con.num_total_features
        sub.feature_names = con.feature_names
        sub.config = con.config
        sub.bin_mappers = con.bin_mappers
        sub.used_feature_map = con.used_feature_map
        n_pad = _round_up(max(len(idx), 1), max(
            int(con.config.tpu_row_block), 128))
        sub.num_data_padded = n_pad
        sub.max_num_bin = con.max_num_bin
        sub.bins = np.zeros((con.bins.shape[0], n_pad), dtype=con.bins.dtype)
        sub.bins[:, :len(idx)] = con.bins[:, :con.num_data][:, idx]
        sub.metadata = con.metadata.subset(idx)
        out = Dataset._from_constructed(sub, params or self.params)
        out.used_indices = idx
        out.reference = self
        return out

    def add_features_from(self, other: "Dataset") -> "Dataset":
        """Concatenate ``other``'s features onto this dataset in place."""
        a = self.construct()._constructed
        b = other.construct()._constructed
        if a.num_data != b.num_data:
            raise ValueError("add_features_from: datasets have different "
                             f"row counts ({a.num_data} vs {b.num_data})")
        fa = a.num_total_features
        n_pad = max(a.num_data_padded, b.num_data_padded)
        fu = a.num_used_features + b.num_used_features
        fu_pad = _round_up(max(fu, 1), _ConstructedDataset.FEATURE_TILE)
        dtype = np.uint8 if max(a.max_num_bin, b.max_num_bin) <= 256 \
            else np.uint16
        bins = np.zeros((fu_pad, n_pad), dtype=dtype)
        bins[:a.num_used_features, :a.num_data] = \
            a.bins[:a.num_used_features, :a.num_data]
        bins[a.num_used_features:fu, :b.num_data] = \
            b.bins[:b.num_used_features, :b.num_data]
        a.bins = bins
        a.num_data_padded = n_pad
        a.bin_mappers = list(a.bin_mappers) + list(b.bin_mappers)
        a.used_feature_map = np.concatenate(
            [a.used_feature_map, b.used_feature_map + fa]).astype(np.int32)
        a.num_total_features = fa + b.num_total_features
        a.feature_names = list(a.feature_names) + list(b.feature_names)
        a.max_num_bin = max(a.max_num_bin, b.max_num_bin)
        a._device_bins = None
        a._feature_meta = None
        a._binner_arrays = None
        return self


def _float_matrix(data) -> np.ndarray:
    """Raw values as the binning reads them: float64, but for a float32
    array, which stays as it is.  Binning widens it block by block
    (``_bin_all``), to the same values and the same bins; a float64 copy of
    a 53M x 67 table is 28 GB that nothing else needs."""
    if isinstance(data, np.ndarray) and data.dtype == np.float32:
        return data
    return np.ascontiguousarray(data, dtype=np.float64)


class _ConstructedDataset:
    """The materialized binned dataset.

    Attributes
    ----------
    bins : np.ndarray  (num_used_features, num_rows_padded) uint8/uint16
        Bin codes, feature-major for row-block streaming into kernels.
    bin_mappers : list[BinMapper]  (per used feature)
    used_feature_map : np.ndarray  original feature idx per used feature
    """

    def __init__(self) -> None:
        self.bins: np.ndarray = None
        self.bin_mappers: List[BinMapper] = []
        self.used_feature_map: np.ndarray = None
        self.num_data: int = 0
        self.num_data_padded: int = 0
        self.num_total_features: int = 0
        self.feature_names: List[str] = []
        self.metadata: Metadata = None
        self.max_num_bin: int = 1
        self.config: Config = None
        self._device_bins = None

    # -- binning (DatasetLoader::CostructFromSampleData, dataset_loader.cpp:535) --

    @classmethod
    def from_matrix(cls, mat: np.ndarray, cfg: Config,
                    categorical: Sequence[int] = (),
                    feature_names: Optional[List[str]] = None) -> "_ConstructedDataset":
        self = cls()
        mat = _float_matrix(mat)
        n, f = mat.shape
        self.num_data = n
        self.num_total_features = f
        self.feature_names = feature_names or [f"Column_{i}" for i in range(f)]
        self.config = cfg
        self.metadata = Metadata(n)
        categorical = set(categorical)

        # sample rows for bin finding (`dataset_loader.cpp:583-618`): the
        # reference samples `bin_construct_sample_cnt` rows with its own PRNG;
        # we use numpy's generator seeded with data_random_seed.
        sample_idx = cls._sample_indices(n, cfg)
        sample = np.asarray(mat if sample_idx is None else mat[sample_idx],
                            dtype=np.float64)

        self._find_mappers(sample, cfg, categorical)
        self._bin_all(mat, cfg)
        return self

    @staticmethod
    def _sample_indices(n: int, cfg: Config) -> Optional[np.ndarray]:
        """Row indices sampled for bin finding, or None for "all rows" —
        ONE definition shared by the in-memory, out-of-core and distributed
        (`io/distributed.py`) loaders so their mapper tables are
        bit-identical by construction."""
        if n > cfg.bin_construct_sample_cnt:
            rng = np.random.RandomState(cfg.data_random_seed)
            return np.sort(rng.choice(n, cfg.bin_construct_sample_cnt,
                                      replace=False))
        return None

    def _find_mappers(self, sample: np.ndarray, cfg: Config,
                      categorical) -> None:
        """FindBin over the sample matrix → ``bin_mappers`` +
        ``used_feature_map`` (trivial features dropped)."""
        categorical = set(categorical)
        self.bin_mappers = []
        keep: List[int] = []
        from .binning import kZeroThreshold
        for j in range(self.num_total_features):
            m = BinMapper()
            col = sample[:, j]
            # the reference samples only non-zero/NaN values and lets FindBin
            # infer the zero count from total_sample_cnt
            # (`dataset_loader.cpp:815`, `c_api.cpp:565`) — bin boundaries
            # depend on this, so match it exactly.
            col = col[(np.abs(col) > kZeroThreshold) | np.isnan(col)]
            m.find_bin(col, total_sample_cnt=len(sample),
                       max_bin=cfg.max_bin, min_data_in_bin=cfg.min_data_in_bin,
                       min_split_data=cfg.min_data_in_leaf,
                       bin_type=BIN_CATEGORICAL if j in categorical else BIN_NUMERICAL,
                       use_missing=cfg.use_missing,
                       zero_as_missing=cfg.zero_as_missing)
            if not m.is_trivial:
                keep.append(j)
                self.bin_mappers.append(m)
        self.used_feature_map = np.asarray(keep, dtype=np.int32)

    @classmethod
    def from_stream(cls, path: str, params: Optional[Dict], cfg: Config,
                    categorical: Sequence[int] = (),
                    feature_names: Optional[List[str]] = None,
                    rank: int = 0, num_machines: int = 1,
                    pre_partition: bool = False,
                    info=None) -> "_ConstructedDataset":
        """Out-of-core construction of a file-backed dataset (the reference's
        ``two_round`` loading, `dataset_loader.cpp:133` + `config.h:227`,
        re-shaped for the padded device word layout):

          * pass 0 — ``scan_data_file``: row count + format, O(1) memory;
          * pass 1 — stream chunks collecting ONLY the
            ``bin_construct_sample_cnt`` sampled rows (the exact
            ``_sample_indices`` sequence of the in-memory path), then FindBin
            on that sample → mappers bit-identical to ``from_matrix``;
          * pass 2 — re-stream, bin each chunk with the global mapper table,
            keep rows with ``global_row % num_machines == rank``
            (``CheckOrPartition`` mod-dealing; all rows when single-machine
            or ``pre_partition``; whole-query dealing with a ``.query``
            sidecar) and pack them straight into the padded ``bins`` words.

        Peak host memory is O(chunk + sample + local binned shard) — the
        full float64 matrix never exists.  Words and mappers are
        bit-identical to ``from_matrix`` on the same file
        (`tests/test_out_of_core.py`)."""
        from .io.parser import _load_sidecar, iter_data_chunks, scan_data_file

        params = dict(params or {})
        if info is None:
            info = scan_data_file(path, params)
        n, f = info.num_rows, info.num_features
        self = cls()
        self.num_total_features = f
        self.feature_names = list(feature_names) if feature_names \
            else [f"Column_{i}" for i in range(f)]
        self.config = cfg
        chunk_rows = max(int(cfg.stream_chunk_rows), 1)

        # ingestion-chunk spans on the pod flight recorder: the engine
        # registers its TraceRecorder globally BEFORE dataset construction
        # (streaming happens inside Booster.__init__, before any telemetry
        # object exists), so every host's trace shows where its load time
        # went chunk by chunk.  None when tracing is off — zero overhead.
        from .observability.trace import get_global_tracer
        tracer = get_global_tracer()

        # ---- pass 1: the from_matrix sample, collected chunk-wise
        sample_idx = self._sample_indices(n, cfg)
        parts: List[np.ndarray] = []
        _t0 = time.perf_counter() if tracer is not None else 0.0
        for start, mat, _lab in iter_data_chunks(path, params, chunk_rows,
                                                 info=info):
            if sample_idx is None:
                parts.append(mat)
            else:
                lo = np.searchsorted(sample_idx, start)
                hi = np.searchsorted(sample_idx, start + len(mat))
                if hi > lo:
                    parts.append(mat[sample_idx[lo:hi] - start])
            if tracer is not None:
                tracer.add_complete(
                    "ingest.sample_chunk", _t0,
                    time.perf_counter() - _t0, cat="ingest",
                    args={"start": int(start), "rows": int(len(mat))})
                _t0 = time.perf_counter()
        sample = np.concatenate(parts, axis=0) if parts \
            else np.zeros((0, f), dtype=np.float64)
        parts = None
        self._find_mappers(sample, cfg, categorical)

        # ---- row ownership (`io/distributed.py` partition semantics)
        full_weight = _load_sidecar(path + ".weight")
        full_group = _load_sidecar(path + ".query")
        qgroup = None
        if num_machines > 1 and not pre_partition:
            if full_group is not None:
                from .io.distributed import partition_queries
                owned, qgroup = partition_queries(full_group, rank,
                                                  num_machines)
            else:
                owned = np.arange(rank, n, num_machines, dtype=np.int64)
        else:
            owned = np.arange(n, dtype=np.int64)
        if full_group is not None and int(np.sum(full_group)) != n:
            raise ValueError(f"query file rows ({int(np.sum(full_group))}) "
                             f"!= data rows ({n})")

        # ---- pass 2: bin + pack owned rows directly into device words
        n_local = len(owned)
        self.num_data = n_local
        block = max(int(cfg.tpu_row_block), 128)
        self.num_data_padded = _round_up(max(n_local, 1), block)
        self.max_num_bin = max((m.num_bin for m in self.bin_mappers),
                               default=1)
        dtype = np.uint8 if self.max_num_bin <= 256 else np.uint16
        fu_pad = _round_up(max(len(self.bin_mappers), 1), self.FEATURE_TILE)
        self.bins = np.zeros((fu_pad, self.num_data_padded), dtype=dtype)
        labels = np.zeros(n_local, dtype=np.float64)
        dst = 0
        _t0 = time.perf_counter() if tracer is not None else 0.0
        for start, mat, lab in iter_data_chunks(path, params, chunk_rows,
                                                info=info):
            lo = np.searchsorted(owned, start)
            hi = np.searchsorted(owned, start + len(mat))
            if hi <= lo:
                continue
            rows = owned[lo:hi] - start
            sub = mat[rows]
            for k, m in enumerate(self.bin_mappers):
                j = int(self.used_feature_map[k])
                self.bins[k, dst:dst + len(rows)] = \
                    m.values_to_bins(sub[:, j]).astype(dtype)
            labels[dst:dst + len(rows)] = lab[rows]
            dst += len(rows)
            if tracer is not None:
                tracer.add_complete(
                    "ingest.bin_chunk", _t0,
                    time.perf_counter() - _t0, cat="ingest",
                    args={"start": int(start), "owned": int(len(rows))})
                _t0 = time.perf_counter()
        if dst != n_local:
            raise ValueError(f"stream produced {dst} owned rows, "
                             f"expected {n_local} — file changed mid-load?")

        self.metadata = Metadata(n_local)
        self.metadata.set_label(labels)
        if full_weight is not None:
            self.metadata.set_weights(full_weight[owned])
        if qgroup is not None:
            self.metadata.set_group(qgroup)
        elif full_group is not None:
            self.metadata.set_group(full_group)
        self.bundle = None
        self._maybe_bundle(cfg, is_reference_linked=(num_machines > 1))
        if num_machines > 1:
            self.global_rows = owned
            self.row_offset = 0
            self.num_data_global = n
        return self

    @classmethod
    def from_reference(cls, mat: np.ndarray, ref: "_ConstructedDataset",
                       cfg: Config) -> "_ConstructedDataset":
        """Validation data binned with the training set's mappers
        (`basic.py:729` reference= semantics)."""
        self = cls()
        mat = _float_matrix(mat)
        n, f = mat.shape
        if f != ref.num_total_features:
            raise ValueError(f"validation data has {f} features, train has "
                             f"{ref.num_total_features}")
        self.num_data = n
        self.num_total_features = f
        self.feature_names = ref.feature_names
        self.config = ref.config
        self.metadata = Metadata(n)
        self.bin_mappers = ref.bin_mappers
        self.used_feature_map = ref.used_feature_map
        self._bin_all(mat, cfg, is_reference_linked=True)
        return self

    FEATURE_TILE = 8  # feature-axis padding multiple for the Pallas kernel

    def _bin_all(self, mat: np.ndarray, cfg: Config,
                 is_reference_linked: bool = False) -> None:
        n = self.num_data
        block = max(int(cfg.tpu_row_block), 128)
        self.num_data_padded = _round_up(max(n, 1), block)
        self.max_num_bin = max((m.num_bin for m in self.bin_mappers), default=1)
        dtype = np.uint8 if self.max_num_bin <= 256 else np.uint16
        fu = len(self.bin_mappers)
        fu_pad = _round_up(max(fu, 1), self.FEATURE_TILE)
        self.bins = np.zeros((fu_pad, self.num_data_padded), dtype=dtype)
        cols = [int(j) for j in self.used_feature_map[:fu]]

        def bin_rows(span):
            # one pass over the rows: a block is transposed once, so that
            # every column is read from contiguous memory (column by column
            # over the whole row-major matrix, each of the 67 columns of a
            # 53M-row table dragged all 28 GB through the cache: 392 s)
            a, b = span
            blk = np.ascontiguousarray(mat[a:b].T, dtype=np.float64)
            for k, m in enumerate(self.bin_mappers):
                self.bins[k, a:b] = m.values_to_bins(blk[cols[k]])

        step = 1 << 17
        spans = [(a, min(a + step, n)) for a in range(0, n, step)]
        from concurrent.futures import ThreadPoolExecutor
        workers = max(1, min(len(spans), os.cpu_count() or 1))
        with ThreadPoolExecutor(max_workers=workers) as pool:
            list(pool.map(bin_rows, spans))   # numpy drops the GIL in its passes
        self.bundle = None
        self._maybe_bundle(cfg, is_reference_linked=is_reference_linked)

    def _maybe_bundle(self, cfg: Config, is_reference_linked: bool = False
                      ) -> None:
        """EFB over the binned matrix, gated exactly as the serial training
        path consumes it — valid sets (reference-linked) and rank-local
        shards skip the exclusivity scan entirely."""
        if not is_reference_linked \
                and cfg.enable_bundle and cfg.tree_learner == "serial" \
                and cfg.tpu_learner in ("auto", "wave", "compact") \
                and self.max_num_bin <= 256 and len(self.bin_mappers) > 1:
            from .efb import find_bundles, apply_bundles
            groups = find_bundles(self, cfg)
            if any(len(g) > 1 for g in groups):
                self.bundle = apply_bundles(self, groups)

    # -- binary cache format -------------------------------------------------

    BINARY_VERSION = 1

    def save_binary(self, filename: str) -> None:
        """Serialize the constructed (binned) dataset — reloading skips
        find-bin + binning entirely (`dataset.h:394` SaveBinaryFile).
        Atomic (tmp + ``os.replace``): a preempted save never leaves a
        truncated cache a later run would fail to load."""
        import json
        import os

        md = self.metadata
        tmp = filename + ".tmp"
        with open(tmp, "wb") as fh:  # np.savez appends .npz to names
            np.savez_compressed(
                fh,
                lgbt_binary_version=np.int64(self.BINARY_VERSION),
                bins=self.bins,
                used_feature_map=self.used_feature_map,
                num_data=np.int64(self.num_data),
                num_total_features=np.int64(self.num_total_features),
                max_num_bin=np.int64(self.max_num_bin),
                feature_names=np.asarray(self.feature_names, dtype=object),
                mappers=np.asarray(
                    json.dumps([m.to_dict() for m in self.bin_mappers]),
                    dtype=object),
                label=md.label,
                weights=(md.weights if md.weights is not None
                         else np.zeros(0, np.float32)),
                query_boundaries=(md.query_boundaries
                                  if md.query_boundaries is not None
                                  else np.zeros(0, np.int32)),
                init_score=(md.init_score if md.init_score is not None
                            else np.zeros(0, np.float64)))
        os.replace(tmp, filename)

    @classmethod
    def load_binary(cls, filename: str, cfg: Config) -> "_ConstructedDataset":
        import json

        z = np.load(filename, allow_pickle=True)
        if int(z["lgbt_binary_version"]) > cls.BINARY_VERSION:
            raise ValueError("binary dataset written by a newer version")
        self = cls()
        self.config = cfg
        self.bins = z["bins"]
        self.used_feature_map = z["used_feature_map"]
        self.num_data = int(z["num_data"])
        self.num_data_padded = self.bins.shape[1]
        self.num_total_features = int(z["num_total_features"])
        self.max_num_bin = int(z["max_num_bin"])
        self.feature_names = [str(s) for s in z["feature_names"]]
        self.bin_mappers = [BinMapper.from_dict(d)
                            for d in json.loads(str(z["mappers"]))]
        self.metadata = Metadata(self.num_data)
        self.metadata.label = z["label"]
        if len(z["weights"]):
            self.metadata.weights = z["weights"]
        if len(z["query_boundaries"]):
            self.metadata.query_boundaries = z["query_boundaries"]
        if len(z["init_score"]):
            self.metadata.init_score = z["init_score"]
        return self

    @staticmethod
    def is_binary_file(path: str) -> bool:
        try:
            with open(path, "rb") as fh:
                magic = fh.read(4)
            if magic[:2] != b"PK":
                return False
            with np.load(path, allow_pickle=True) as z:
                return "lgbt_binary_version" in z
        except Exception:
            return False

    # -- device placement ----------------------------------------------------

    def device_bins(self):
        """Binned matrix as a device array (uint8 in HBM), cached."""
        if self._device_bins is None:
            import jax.numpy as jnp
            self._device_bins = jnp.asarray(self.bins)
        return self._device_bins

    @property
    def num_used_features(self) -> int:
        return len(self.bin_mappers)

    def binner_arrays(self):
        """Padded per-feature boundary/LUT arrays for the vectorized
        predict binner (`serving/binner.py`): boundary rows for the
        device ``searchsorted``, category LUT rows, missing metadata.
        Cached — serving and ``DevicePredictor.predict_raw`` share one
        instance per dataset."""
        from .serving.binner import BinnerArrays

        return BinnerArrays.for_data(self)

    def feature_meta_arrays(self):
        """Static per-feature metadata as numpy arrays for the split finder:
        (num_bin, missing_type, default_bin, is_categorical); cached."""
        if getattr(self, "_feature_meta", None) is None:
            num_bin = np.array([m.num_bin for m in self.bin_mappers],
                               dtype=np.int32)
            missing = np.array([m.missing_type for m in self.bin_mappers],
                               dtype=np.int32)
            default_bin = np.array([m.default_bin for m in self.bin_mappers],
                                   dtype=np.int32)
            is_categorical = np.array([m.bin_type == BIN_CATEGORICAL
                                       for m in self.bin_mappers], dtype=bool)
            self._feature_meta = (num_bin, missing, default_bin, is_categorical)
        return self._feature_meta
