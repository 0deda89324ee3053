"""lightgbm_tpu — a TPU-native gradient-boosted decision tree framework.

A from-scratch re-design of LightGBM (reference mounted at /root/reference)
for TPU hardware: histogram construction runs as MXU one-hot contractions /
Pallas kernels over a dense binned matrix in HBM, split finding is a
vectorized cumsum scan, tree growth is a jitted leaf-wise step, and the
distributed tree learners route histogram reduction through XLA collectives
over ICI instead of the reference's socket/MPI ``Network`` layer.

Public API mirrors `python-package/lightgbm/__init__.py:32-36`.
"""

from .config import Config
from .dataset import Dataset
from .engine import Booster, CVBooster, cv, train
from .callback import (early_stopping, print_evaluation, record_evaluation,
                       record_telemetry, reset_parameter)

try:  # sklearn wrappers are optional on minimal installs
    from .sklearn import (LGBMClassifier, LGBMModel, LGBMRanker,
                          LGBMRegressor)
    _SKLEARN = ["LGBMModel", "LGBMRegressor", "LGBMClassifier", "LGBMRanker"]
except ImportError:  # pragma: no cover
    _SKLEARN = []

try:
    from .plotting import (create_tree_digraph, plot_importance, plot_metric,
                           plot_tree)
    _PLOT = ["plot_importance", "plot_metric", "plot_tree",
             "create_tree_digraph"]
except ImportError:  # pragma: no cover
    _PLOT = []

__version__ = "2.2.4.tpu0"


def use_compile_cache() -> str:
    """Give JAX's persistent compilation cache its ONE place and return it.

    ``JAX_COMPILATION_CACHE_DIR`` decides when it is set (JAX reads the
    variable itself; nothing is set in code).  Otherwise the cache is
    ``<checkout>/.jax_tpu_cache`` — a fixed path, never a temporary name:
    the path is part of the cache key, so a directory that moves never
    hits.  Every entry point that compiles (``train``, the CLI, ``serve``,
    the benchmarks, ``chip_smoke.py``) calls this first."""
    import os

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        import jax
        path = os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), ".jax_tpu_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path


__all__ = ["Dataset", "Booster", "CVBooster", "Config",
           "train", "cv", "use_compile_cache",
           "early_stopping", "print_evaluation", "record_evaluation",
           "record_telemetry", "reset_parameter"] + _SKLEARN + _PLOT
