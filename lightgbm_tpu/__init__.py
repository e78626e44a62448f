"""lightgbm_tpu: a TPU-native gradient-boosting (GBDT) framework.

A from-scratch re-design of LightGBM's capabilities for TPUs: JAX/XLA/Pallas
compute (one-hot MXU histograms, single-program leaf-wise tree growth,
``shard_map`` collectives for distributed training) behind the familiar
LightGBM Python API surface (``Dataset``/``Booster``/``train``/``cv``/sklearn
wrappers).
"""
from .utils import compile_cache as _compile_cache

_compile_cache.configure()

from .obs import install_compile_listener as _install_compile_listener  # noqa: E402

_install_compile_listener()     # compilations become lgbm/compile spans

from .basic import Booster, Dataset  # noqa: E402
from .callback import early_stopping, print_evaluation, log_evaluation, \
    record_evaluation, reset_parameter
from .config import Config
from .engine import CVBooster, cv, train
from .utils.log import LightGBMError, register_log_callback

__version__ = "0.1.0"

__all__ = ["Booster", "Dataset", "Config", "CVBooster", "cv", "train",
           "LightGBMError", "register_log_callback", "early_stopping",
           "print_evaluation", "log_evaluation", "record_evaluation",
           "reset_parameter", "__version__"]


def __getattr__(name):
    # lazy imports for optional API surfaces
    if name in ("LGBMModel", "LGBMClassifier", "LGBMRegressor", "LGBMRanker"):
        from . import sklearn as _sk
        return getattr(_sk, name)
    if name in ("serve", "PredictorArtifact", "Predictor", "MicroBatcher",
                "QueueSaturatedError"):
        from . import serve as _serve
        return _serve if name == "serve" else getattr(_serve, name)
    if name in ("DistLGBMClassifier", "DistLGBMRegressor"):
        from .parallel import estimators as _est
        return getattr(_est, name)
    if name == "stream":
        from . import stream as _stream
        return _stream
    if name.startswith("plot_") or name in ("create_tree_digraph", "plotting"):
        import importlib
        _pl = importlib.import_module(".plotting", __name__)
        return _pl if name == "plotting" else getattr(_pl, name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
