"""2-D hybrid data×feature frontier-wave learner (`tree_learner=
data_feature`).

The reference treats data- and feature-parallel as DISJOINT modes
(`src/treelearner/data_parallel_tree_learner.cpp` vs
`feature_parallel_tree_learner.cpp`); on a TPU slice the mesh makes them one
program: each device owns a (feature-word-slice × row-shard) TILE of the
packed bin matrix (``P("feature", "data")`` under
`parallel/sharding.py`'s rules), so at D = Dd×Df devices

  * member histograms cover only ``fs_col = f_pad/Df`` features over
    ``n_pad/Dd`` local rows, and the per-wave ``psum_scatter`` runs along
    the ``data`` axis ONLY — Dd participants moving (W, fs_col, B, 3)
    instead of the 1-D data mode's D participants moving (W, f_pad, B, 3):
    a Df× smaller payload over a Dd-wide group;
  * split scans cover the device's ``fs = fs_col/Dd`` slice of the
    scattered histogram, and the winner merge is ONE joint all_gather over
    BOTH axes of a tiny packed record (``SyncUpGlobalBestSplit``,
    `parallel_tree_learner.h:186-209`) — same wire volume as either 1-D
    mode's merge;
  * the only new exchanges are two tiny per-row word broadcasts along
    ``feature`` (the split feature's packed bin word lives on one feature
    column — the decide pass and the stall partition each psum an
    (rows,)-int32 lane), the price of never replicating bins.

Double-buffered waves (``tpu_wave_hist_buffers``): the W member histograms
of a wave accumulate in B independent half-wave groups, each followed by
its own reduce-scatter.  Group g+1's accumulation has no data dependence
on group g's collective, so XLA's async collectives (TPU: ICI DMA; the
guide's "overlap of collective communication with compute") run the wire
transfer of one group under the VPU/MXU accumulation of the next.  TRUE
cross-wave overlap is impossible by construction — wave k+1's membership
depends on wave k's reduced scans — so the half-wave split is the whole
legal overlap window.

Exactness: same records stream as the serial wave learner
(`tests/test_parallel2d.py`), via the same replicated-bookkeeping argument
as the 1-D modes plus a lowest-feature-index tie-break at the 2-D merge
(tile offsets are not monotone in gathered device order).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from ..config import Config
from ..dataset import _ConstructedDataset
from ..learner_compact import CF_GAIN, CI_FEAT, CompactTPUTreeLearner
from ..learner_wave import WaveState, wave_budget_reason
from ..observability.phases import scope
from .sharding import AXIS_DATA, AXIS_FEATURE
from .wave_sharded import ShardedWaveLearner


def _mesh_dims(mesh: Mesh):
    shape = dict(zip(mesh.axis_names, mesh.devices.shape))
    return int(shape.get(AXIS_DATA, 1)), int(shape.get(AXIS_FEATURE, 1))


class ShardedWave2DLearner(ShardedWaveLearner):
    """One shard_map tree step over a ``("data", "feature")`` mesh (see
    module docstring).  Inheriting from the 1-D data learner with
    ``self.axis = "data"`` keeps every row collective (leaf counts, global
    scalars, span replication, histogram reduce-scatter) on the data axis
    untouched; rebinding ``self.fw`` to the LOCAL tile word count makes the
    inherited sort/partition/histogram machinery tile-local for free."""

    _placement_mode = "data_feature"

    def __init__(self, cfg: Config, data: _ConstructedDataset, mesh: Mesh,
                 hist_backend: str = "auto"):
        self.mesh = mesh
        self.axis = AXIS_DATA          # inherited row seams ride this
        self.faxis = AXIS_FEATURE
        self.Dd, self.Df = _mesh_dims(mesh)
        self.D = self.Dd * self.Df
        CompactTPUTreeLearner.__init__(self, cfg, data, hist_backend)
        if self.n_pad % self.Dd:
            raise ValueError(f"padded rows {self.n_pad} not divisible by "
                             f"data axis {self.Dd}")
        self.n_local = self.n_pad // self.Dd
        f_pad = data.bins.shape[0]
        self.f_pad = f_pad
        fw_global = self.fw            # packed words over ALL features
        if fw_global % self.Df:
            raise ValueError(f"packed words {fw_global} not divisible by "
                             f"feature axis {self.Df} (word-aligned tiles)")
        self.fw_global = fw_global
        self.fws = fw_global // self.Df     # packed words per tile
        self.fs_col = self.fws * 4          # features per feature column
        if self.fs_col % self.Dd:
            raise ValueError(f"feature column {self.fs_col} not divisible "
                             f"by data axis {self.Dd}")
        self.fs = self.fs_col // self.Dd    # scan slice per device
        # rebind to the LOCAL tile: the inherited histogram branches,
        # partition sorts and materialization all read self.fw
        self.fw = self.fws
        self._init_local_windows(cfg, self.n_local)
        self._use_pallas = False
        self._pad_feature_meta(data, f_pad)
        self._sharded_bins = None
        self._jit_tree_c = None
        # wave dims over the local shard (same as the 1-D wave __init__)
        self._init_wave_dims(cfg)
        self.open_levels = 0
        self.fw_col = jnp.arange(self.f_pad, dtype=jnp.int32)
        self.fw_goff = jnp.zeros(self.f_pad, jnp.int32)
        self.fw_bnd = jnp.zeros(self.f_pad, jnp.int32)
        self._jit_tree_w = None
        self._hist_buffers = max(
            int(getattr(cfg, "tpu_wave_hist_buffers", 2)), 1)

    # -- tile geometry --------------------------------------------------------

    def _shard_slice(self, full):
        """This device's scan slice of a global (f_pad,) array: feature
        column j covers [j·fs_col, (j+1)·fs_col); the data-axis scatter
        hands row i the i-th fs-slice of that column."""
        i = lax.axis_index(self.axis)
        j = lax.axis_index(self.faxis)
        return lax.dynamic_slice_in_dim(full, j * self.fs_col + i * self.fs,
                                        self.fs)

    # -- split-word broadcast along the feature axis --------------------------

    def _word_select(self, bins_c, widx_r):
        """Decide-pass word extraction: ``widx_r`` carries GLOBAL packed
        word indices, this device's (fws, rows) chunk holds words
        [j·fws, (j+1)·fws) — masked local sum, then one (rows,)-int32 psum
        along ``feature`` broadcasts the owning column's words."""
        j = lax.axis_index(self.faxis)
        loc = widx_r - j * self.fws
        word = jnp.zeros(widx_r.shape[0], jnp.int32)
        for wdi in range(self.fws):
            word = word + jnp.where(loc == wdi, bins_c[wdi], 0)
        self._rec_coll("psum", word)
        with scope("exchange"):
            return lax.psum(word, self.faxis)

    def _window_word(self, bw, col):
        """Stall-partition word extraction over a sliced (fws, S) window;
        ``col`` is the replicated global packed column, so every device in
        a feature group takes the same branch and the psum pairs up."""
        j = lax.axis_index(self.faxis)
        w = col // 4 - j * self.fws
        S = bw.shape[1]
        safe = jnp.clip(w, 0, self.fws - 1)
        word = lax.dynamic_slice(bw, (safe, jnp.int32(0)), (1, S))[0]
        word = jnp.where((w >= 0) & (w < self.fws), word, 0)
        self._rec_coll("psum", word)
        with scope("exchange"):
            return lax.psum(word, self.faxis)

    # -- best-split merge over BOTH axes --------------------------------------

    def _best_rows_global(self, hist2, crow_sums, fmask_pad, depth_ok,
                          constraints):
        """Local fs-slice scans → ONE joint all_gather over (data, feature)
        → global argmax with an explicit lowest-feature-index tie-break
        (tile offsets are NOT monotone in gathered device order, so the
        1-D learner's positional tie-break does not reproduce the serial
        argmax)."""
        i = lax.axis_index(self.axis)
        j = lax.axis_index(self.faxis)
        goff = j * self.fs_col + i * self.fs

        def one(hist, sg, sh, cn, mn, mx):
            g, thr, dl, ic, bits, lsg, lsh, lcn, rsg, rsh, rcn, lo, ro = \
                self._feature_cands_shard(hist, sg, sh, cn, fmask_pad, mn,
                                          mx)
            bf = jnp.argmax(g).astype(jnp.int32)
            pick = lambda a: a[bf]
            cf = jnp.stack([pick(g).astype(self._acc), pick(lsg), pick(lsh),
                            pick(lcn), pick(rsg), pick(rsh), pick(rcn),
                            pick(lo), pick(ro)]).astype(self._acc)
            flags = pick(dl).astype(jnp.int32) + \
                2 * pick(ic).astype(jnp.int32)
            ci = jnp.stack([bf + goff, pick(thr), flags])
            return cf, ci.astype(jnp.int32), bits[bf]

        sg2, sh2, cn2 = crow_sums
        if constraints is not None:
            mins, maxs = constraints
            cf, ci, cb = jax.vmap(one)(hist2, sg2, sh2, cn2, mins, maxs)
        else:
            cf, ci, cb = jax.vmap(
                lambda h, g, hh, c: one(h, g, hh, c, None, None)
            )(hist2, sg2, sh2, cn2)
        axes = (self.axis, self.faxis)
        for x in (cf, ci, cb):
            self._rec_coll("all_gather", x)
        with scope("exchange"):
            cf_all = lax.all_gather(cf, axes)      # (Dd*Df, K, NUM_CF)
            ci_all = lax.all_gather(ci, axes)
            cb_all = lax.all_gather(cb, axes)
        gains = cf_all[:, :, CF_GAIN]
        max_gain = jnp.max(gains, axis=0)
        at_max = gains == max_gain[None, :]
        feat_masked = jnp.where(at_max, ci_all[:, :, CI_FEAT],
                                jnp.int32(1 << 30))
        win = jnp.argmin(feat_masked, axis=0)
        cf_g = jnp.take_along_axis(cf_all, win[None, :, None], axis=0)[0]
        ci_g = jnp.take_along_axis(ci_all, win[None, :, None], axis=0)[0]
        cb_g = jnp.take_along_axis(cb_all, win[None, :, None], axis=0)[0]
        cf_g = cf_g.at[:, CF_GAIN].set(
            jnp.where(depth_ok, cf_g[:, CF_GAIN], -jnp.inf))
        return cf_g, ci_g, cb_g

    # -- double-buffered wave histograms --------------------------------------

    def _wave_member_hists(self, st: WaveState, sm_slot, sm_start, sm_cnt,
                           valid, ph, lh_w, rh_w, left_small):
        """The W member histograms split into ``tpu_wave_hist_buffers``
        independent groups, each with its own data-axis reduce-scatter:
        group g+1's local accumulation has no dependence on group g's
        collective, so async collectives overlap the wire with compute
        (half-wave double buffering — see module docstring)."""
        def hist_member(_, xs):
            slot, start, cnt, vk = xs

            def compute(_):
                hidx = self._bucket_idx(jnp.maximum(cnt, 1))
                return lax.switch(hidx, self._hist_branches, st.bins_p,
                                  st.w_p, st.lid_p, start, cnt, slot)

            def skip(_):
                b = self.num_bins_padded
                return jnp.zeros((self.fs_col, b, 3), self._hist_dtype())

            return 0, lax.cond(vk, compute, skip, 0)

        W = int(sm_slot.shape[0])
        nb = min(self._hist_buffers, W)
        bounds = [round(g * W / nb) for g in range(nb + 1)]
        parts = []
        for g in range(nb):
            lo, hi = bounds[g], bounds[g + 1]
            if lo == hi:
                continue
            _, h_loc = lax.scan(hist_member, 0,
                                (sm_slot[lo:hi], sm_start[lo:hi],
                                 sm_cnt[lo:hi], valid[lo:hi]))
            parts.append(self._exchange(h_loc, 1))
        h_small = parts[0] if len(parts) == 1 else \
            jnp.concatenate(parts, axis=0)      # (W, fs, B, 3)
        h_par = st.hist_pool[ph]
        h_large = h_par - h_small
        lsm = left_small[:, None, None, None]
        hl = jnp.where(lsm, h_small, h_large)
        hr = jnp.where(lsm, h_large, h_small)
        pool = st.hist_pool.at[lh_w].set(hl).at[rh_w].set(hr)
        return pool, hl, hr

    # -- host orchestration ---------------------------------------------------

    def _bins_spec(self) -> P:
        return P(self.faxis, self.axis)     # a tile a device

    def exchange_probe(self):
        """The 2D learner's dominant wire: the per-wave data-axis
        reduce-scatter at the LOCAL feature-column shape, entered over
        the full 2D mesh (the feature axis rides along replicated, as in
        the real program)."""
        if getattr(self, "_probe_fn", None) is None:
            return self._probe_program(
                lambda h: self._exchange(h, 1), P(),
                P(None, self.axis),
                (jnp.zeros((self.W, self.fs_col, self.num_bins_padded, 3),
                           self._hist_dtype()),))
        return self._probe_fn, self._probe_args


def wave2d_ineligible_reason(cfg: Config, data: _ConstructedDataset,
                             mesh: Mesh) -> Optional[str]:
    """Why ``tree_learner=data_feature`` cannot run on this mesh/dataset
    (None = eligible).  Divisibility mirrors the tile geometry above; the
    byte gate reuses the serial wave budget at the LOCAL tile shape."""
    if cfg.tpu_learner not in ("auto", "wave"):
        return f"tpu_learner={cfg.tpu_learner} (2D mode is wave-only)"
    if data.max_num_bin > 256:
        return f"max_num_bin {data.max_num_bin} > 256"
    dd, df = _mesh_dims(mesh)
    n_pad = int(data.num_data_padded)
    f_pad = int(data.bins.shape[0])
    if f_pad % 4:
        return f"padded features {f_pad} not word-aligned"
    if n_pad % max(dd, 1):
        return f"padded rows {n_pad} % data axis {dd} != 0"
    fw = f_pad // 4
    if fw % max(df, 1):
        return f"packed words {fw} % feature axis {df} != 0"
    fs_col = (fw // max(df, 1)) * 4
    if fs_col % max(dd, 1):
        return f"feature column {fs_col} % data axis {dd} != 0"
    return wave_budget_reason(cfg, n_pad // max(dd, 1), fs_col,
                              int(data.max_num_bin))


def wave2d_eligible(cfg: Config, data: _ConstructedDataset,
                    mesh: Mesh) -> bool:
    return wave2d_ineligible_reason(cfg, data, mesh) is None
