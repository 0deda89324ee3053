"""Lambdarank NDCG objective — queries bucketed by their own length.

TPU-native re-design of ``LambdarankNDCG``
(`src/objective/rank_objective.hpp:19-228`).  The reference runs a per-query
O(n²) pairwise scalar loop under OpenMP.  Here every query is padded to its
LENGTH CLASS, the power of two at or above its length (at least 8); the
queries of one class are batched as ``(qb, Q, Q)`` element-wise passes, which
the VPU eats, with ``qb`` set by a fixed element budget; and each class is
its own pass.  A query of 20 documents costs 32² pair slots, not the longest
query's square.

**Layout** (built once in ``init``, on the host).  Every document has one
SLOT in a flat array ordered by class, batch, query and position in the
query; a class holds ``nb * qb * Q`` slots, its queries padded with empty
ones to a multiple of ``qb``.  An iteration gathers the scores into slot
order (``rank_order``), sorts each query by score and computes its pairs
(``rank_order``, ``rank_pairs``), and gathers every row's lambda and hessian
back from its slot (``rank_writeback``): the layout's inverse permutation,
once an iteration.  The tables are device arrays the objective declares
(``TABLE_ARRAYS``), so a jitted caller takes them as ARGUMENTS: a second
label vector or query layout of the same class shapes reuses its program.

Semantics preserved: rank discounts 1/log2(2+pos) over a stable sort by
descending score (`rank_objective.hpp:100-104`), per-pair ΔNDCG with the
max-DCG@k normalization (``CalMaxDCGAtK``, `src/metric/dcg_calculator.cpp`,
k = ``max_position``), ``label_gain``, the ``(0.01+|Δscore|)``
regularization when a query's scores are not all equal, the
``p_hessian = p_lambda·(2-p_lambda)`` curvature and per-document weights.

Departures, each deliberate:

* the sigmoid lookup table (`rank_objective.hpp:180-193`) is replaced by the
  exact expression ``2 / (1 + exp(2·σ·Δ))``, the function the table samples;
* a document's lambdas and hessians are summed in float32 over its row of
  the query's pair matrix (the reference accumulates the high document's
  sums in double, the low document's in float32, in rank order);
* each unordered pair is evaluated twice, once from either document's row,
  with the sign of the label difference choosing the orientation: the pair
  pass reduces along one axis only.
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from .config import Config
from .objectives import ObjectiveFunction
from .observability.phases import scope

# element budget of one batch's (qb, Q, Q) pair pass (256 MiB of f32 if it
# were written out; the pass reduces it inside one fusion)
PAIR_BUDGET = 1 << 26
MIN_CLASS = 8


def default_label_gain(max_label: int = 31) -> np.ndarray:
    """2^i - 1 (`DCGCalculator::DefaultLabelGain`)."""
    return (2.0 ** np.arange(max_label + 1)) - 1.0


def length_class(sizes: np.ndarray) -> np.ndarray:
    """The padded length of each query: the power of two at or above its
    length, at least ``MIN_CLASS``."""
    sizes = np.maximum(np.asarray(sizes, np.int64), 1)
    return np.maximum(MIN_CLASS, 2 ** np.ceil(np.log2(sizes)).astype(np.int64))


def pair_layout(sizes: np.ndarray, budget: int = PAIR_BUDGET):
    """Classes of the layout, smallest first: ``(Q, qb, nb, slot_offset,
    query_offset, queries)`` with ``queries`` the query numbers of the class
    in their order; and the layout's counters."""
    sizes = np.asarray(sizes, np.int64)
    cls = length_class(sizes)
    classes, slot_off, query_off = [], 0, 0
    for Q in np.unique(cls):
        Q = int(Q)
        queries = np.flatnonzero(cls == Q)
        qb = max(1, min(len(queries), budget // (Q * Q)))
        nb = -(-len(queries) // qb)
        qb = -(-len(queries) // nb)          # the fewest empty queries
        classes.append((Q, qb, nb, slot_off, query_off, queries))
        slot_off += nb * qb * Q
        query_off += nb * qb
    counters = {
        "real_pairs": int(np.sum(sizes * sizes)),
        "computed_pairs": int(sum(nb * qb * Q * Q
                                  for Q, qb, nb, *_ in classes)),
        "classes": len(classes),
        "longest_query": int(sizes.max()) if sizes.size else 0,
        "slots": int(slot_off),
    }
    return classes, counters


class LambdarankNDCG(ObjectiveFunction):
    name = "lambdarank"
    need_group = True
    # the layout's device tables: not row-aligned (``doc_slot`` has a row
    # axis but indexes slots), never sharded by rows
    TABLE_ARRAYS = ("slot_doc", "slot_label", "query_inv", "doc_slot")

    def __init__(self, cfg: Config):
        super().__init__(cfg)
        if cfg.sigmoid <= 0:
            raise ValueError("Sigmoid param should be greater than zero")
        self.sigmoid = float(cfg.sigmoid)
        lg = cfg.label_gain
        self.label_gain = np.asarray(lg, dtype=np.float64) if lg \
            else default_label_gain()
        self.optimize_pos_at = cfg.max_position

    def init(self, metadata, num_data, num_data_padded):
        super().init(metadata, num_data, num_data_padded)
        qb = metadata.query_boundaries
        if qb is None:
            raise ValueError("Lambdarank tasks require query information")
        sizes = np.diff(qb).astype(np.int64)
        nq = len(sizes)
        n = num_data
        lab_int = np.asarray(metadata.label).astype(np.int64)
        if lab_int.size and int(lab_int.max()) >= len(self.label_gain):
            raise ValueError(
                f"Label {int(lab_int.max())} exceeds label_gain size "
                f"{len(self.label_gain)}; set label_gain explicitly")
        lab_int = np.clip(lab_int, 0, None)
        qid = np.repeat(np.arange(nq, dtype=np.int64), sizes)
        within = np.arange(n, dtype=np.int64) - np.asarray(qb)[qid]

        classes, self.pair_layout = pair_layout(sizes)
        S = self.pair_layout["slots"]
        n_query_slots = sum(nb * q_b for _, q_b, nb, *_ in classes)
        # each query's first slot, and its place in the padded query list
        q_slot0 = np.zeros(nq, np.int64)
        q_place = np.zeros(nq, np.int64)
        for Q, _, _, soff, qoff, queries in classes:
            j = np.arange(len(queries), dtype=np.int64)
            q_slot0[queries] = soff + j * Q
            q_place[queries] = qoff + j
        slot = q_slot0[qid] + within                      # (n,) doc -> slot
        self._classes = tuple((Q, q_b, nb, soff, qoff)
                              for Q, q_b, nb, soff, qoff, _ in classes)
        slot_doc = np.zeros(S, np.int32)
        slot_doc[slot] = np.arange(n, dtype=np.int32)
        slot_label = np.full(S, -1, np.int32)
        slot_label[slot] = lab_int.astype(np.int32)
        # padded rows point at the zero slot appended after the last class
        doc_slot = np.full(num_data_padded, S, np.int32)
        doc_slot[:n] = slot
        # max DCG@k per query, vectorized: one stable (qid, -label) sort
        ideal = np.lexsort((-lab_int, qid))
        disc = 1.0 / np.log2(within + 2.0)
        k = self.optimize_pos_at
        gains = self.label_gain[lab_int[ideal]] * disc * (within < k)
        maxdcg = np.bincount(qid, weights=gains, minlength=nq)
        inv = np.where(maxdcg > 0, 1.0 / np.where(maxdcg > 0, maxdcg, 1.0),
                       0.0)
        query_inv = np.zeros(n_query_slots, np.float32)
        query_inv[q_place] = inv
        self.slot_doc = jnp.asarray(slot_doc)
        self.slot_label = jnp.asarray(slot_label)
        self.query_inv = jnp.asarray(query_inv)
        self.doc_slot = jnp.asarray(doc_slot)

    # -- device computation --------------------------------------------------

    def _batch(self, s, lab, inv):
        """Lambdas and hessians of ``qb`` queries padded to one class
        (`rank_objective.hpp:79-164` GetGradientsForOneQuery): ``s``, ``lab``
        (qb, Q) with label -1 on empty slots, ``inv`` (qb,) 1/maxDCG@k."""
        valid = lab >= 0
        neg_inf = jnp.float32(-np.inf)
        s = jnp.where(valid, s, neg_inf)
        with scope("rank_order"):
            # rank position of each doc (stable sort by descending score)
            order = jnp.argsort(-s, axis=-1, stable=True)        # pos -> doc
            pos = jnp.argsort(order, axis=-1)                    # doc -> pos
        with scope("rank_pairs"):
            discount = 1.0 / jnp.log2(pos.astype(jnp.float32) + 2.0)
            gain_lut = jnp.asarray(self.label_gain.astype(np.float32))
            gains = gain_lut[jnp.clip(lab, 0, len(self.label_gain) - 1)]
            best = jnp.max(s, axis=-1)
            worst = jnp.min(jnp.where(valid, s, jnp.inf), axis=-1)
            norm = (best != worst)[:, None, None]
            # sign of the label difference orients each ordered pair: +1
            # where the row's document is the pair's high one
            sign = jnp.sign(lab[:, :, None] - lab[:, None, :]).astype(
                jnp.float32)
            pair = (sign != 0) & valid[:, :, None] & valid[:, None, :]
            ds = s[:, :, None] - s[:, None, :]
            dgap = sign * (gains[:, :, None] - gains[:, None, :])
            pdisc = jnp.abs(discount[:, :, None] - discount[:, None, :])
            delta = dgap * pdisc * inv[:, None, None]
            delta = jnp.where(norm, delta / (0.01 + jnp.abs(ds)), delta)
            # sigmoid of the high document's score minus the low one's
            sig = 2.0 / (1.0 + jnp.exp(2.0 * self.sigmoid * sign * ds))
            lam = jnp.sum(jnp.where(pair, -sign * delta * sig, 0.0), axis=-1)
            hes = jnp.sum(jnp.where(pair, 2.0 * delta * sig * (2.0 - sig),
                                    0.0), axis=-1)
        return lam, hes

    def _class_pass(self, s_all, Q, qb, nb, soff, qoff):
        n_slots = nb * qb * Q
        s = lax.slice_in_dim(s_all, soff, soff + n_slots).reshape(nb, qb, Q)
        lab = lax.slice_in_dim(self.slot_label, soff, soff + n_slots) \
            .reshape(nb, qb, Q)
        inv = lax.slice_in_dim(self.query_inv, qoff, qoff + nb * qb) \
            .reshape(nb, qb)
        if nb == 1:
            lam, hes = self._batch(s[0], lab[0], inv[0])
        else:
            lam, hes = lax.map(lambda a: self._batch(*a), (s, lab, inv))
        return lam.reshape(-1), hes.reshape(-1)

    def get_gradients(self, score, class_id=0):
        with scope("rank_order"):
            s_all = score[self.slot_doc]          # scores in slot order
        lams, hess = [], []
        for Q, qb, nb, soff, qoff in self._classes:
            lam, hes = self._class_pass(s_all, Q, qb, nb, soff, qoff)
            lams.append(lam)
            hess.append(hes)
        zero = [jnp.zeros(1, jnp.float32)]
        with scope("rank_writeback"):
            g = jnp.concatenate(lams + zero)[self.doc_slot]
            h = jnp.concatenate(hess + zero)[self.doc_slot]
        if self.weights is not None:
            g, h = g * self.weights, h * self.weights
        return g, h
