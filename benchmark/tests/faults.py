"""Faults a training cell can have, planted in the program under the timed
path.  Each is a context manager that patches and restores; each covers the
fused pipelined iteration and the synchronous one."""

import contextlib

import numpy as np


@contextlib.contextmanager
def _patched(*targets):
    """``targets``: (class, method name, make(orig) -> replacement)."""
    saved = []
    for cls, name, make in targets:
        orig = getattr(cls, name)
        saved.append((cls, name, orig))
        setattr(cls, name, make(orig))
    try:
        yield
    finally:
        for cls, name, orig in saved:
            setattr(cls, name, orig)


def state_unchanged():
    """The step returns the train score it was given."""
    from lightgbm_tpu.boosting.gbdt import GBDT, ScoreUpdater

    def fused(orig):
        def patched(self):
            fn = orig(self)

            def step(score, *rest):
                out = fn(score + 0.0, *rest)
                return (score,) + tuple(out[1:])
            return step
        return patched

    def sync(orig):
        return lambda self, *a, **kw: None
    return _patched((GBDT, "_fused_iter_fn", fused),
                    (ScoreUpdater, "add_by_leaf_id", sync))


def half_batch():
    """Half of the rows are left out of every tree; sums are over the rest."""
    from lightgbm_tpu.boosting.gbdt import GBDT

    def make(orig):
        def patched(self, *a, **kw):
            if getattr(self, "_bench_half", None) is None:
                mask = np.asarray(self._valid_rows).astype(np.float32)
                mask[: mask.size // 2] = 0
                self._bench_half = self._place_rows(mask)
            self._bag_mask = self._bench_half
            return orig(self, *a, **kw)
        return patched
    return _patched((GBDT, "_train_trees_fused", make),
                    (GBDT, "_train_trees", make))


def answer_altered():
    """A leaf value altered where the host tree is produced."""
    from lightgbm_tpu.learner_compact import CompactTPUTreeLearner
    from lightgbm_tpu.learner_wave import WaveTPUTreeLearner

    def pipelined(orig):
        def patched(self, *a, **kw):
            tree = orig(self, *a, **kw)
            tree.leaf_value[0] *= 1.02
            return tree
        return patched

    def sync(orig):
        def patched(self, *a, **kw):
            tree, leaf_id = orig(self, *a, **kw)
            tree.leaf_value[0] *= 1.02
            return tree, leaf_id
        return patched
    return _patched((WaveTPUTreeLearner, "assemble_host", pipelined),
                    (CompactTPUTreeLearner, "train", sync))


def predict_altered():
    """A prediction altered where ``Booster.predict`` hands it over."""
    from lightgbm_tpu.engine import Booster

    def make(orig):
        def patched(self, *a, **kw):
            return orig(self, *a, **kw) * 0.99
        return patched
    return _patched((Booster, "predict", make))


def valid_unchanged():
    """The validation score is not moved by the new tree (a fault only a
    cell with a validation set can have)."""
    from lightgbm_tpu.boosting.gbdt import ScoreUpdater

    def make(orig):
        return lambda self, *a, **kw: None
    return _patched((ScoreUpdater, "add_by_tree", make))


FAULTS = {f.__name__: f for f in (state_unchanged, half_batch, answer_altered,
                                  predict_altered)}
VALID_FAULTS = dict(FAULTS, valid_unchanged=valid_unchanged)
