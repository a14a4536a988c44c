"""LightGBM-TPU: a TPU-native gradient boosting framework.

A from-scratch re-design of LightGBM's capabilities (reference surveyed in
SURVEY.md) on JAX/XLA: histogram GBDT with leaf-wise growth compiled to TPU
(MXU one-hot-matmul histograms, vectorized bin-scan split finding, whole-tree
growth under one jit), mesh-sharded data/feature/voting-parallel training via
jax collectives, and the reference's public Python surface::

    import lightgbm_tpu as lgb
    bst = lgb.train({"objective": "binary"}, lgb.Dataset(X, y))
    bst.predict(X)
"""

from . import analysis, distributed, ingest, resilience, telemetry
from .basic import Booster
from .callback import (EarlyStopException, early_stopping, log_evaluation,
                       print_evaluation, record_evaluation, reset_parameter)
from .config import Config
from .dataset import Dataset
from .engine import CVBooster, cv, train
from .ingest import StreamedDataset, train_streamed
from .models.model_text import ModelCorruptError
from .multitrain import ManyBooster, MultiTrainError, train_many
from .resilience import (Checkpoint, CheckpointError, TrainingPreempted,
                         load_checkpoint)
from .utils.log import register_log_callback, set_verbosity

try:
    from .sklearn import (LGBMClassifier, LGBMModel, LGBMRanker,
                          LGBMRegressor)
    _SKLEARN_OK = True
except ImportError:  # sklearn not installed
    _SKLEARN_OK = False

from .plotting import (plot_importance, plot_metric, plot_tree,
                       plot_split_value_histogram, create_tree_digraph)

__version__ = "0.1.0"

__all__ = ["Dataset", "Booster", "Config", "train", "cv", "CVBooster",
           "train_many", "ManyBooster", "MultiTrainError",
           "early_stopping", "print_evaluation", "log_evaluation",
           "record_evaluation", "reset_parameter", "EarlyStopException",
           "register_log_callback", "set_verbosity", "analysis",
           "distributed", "ingest", "StreamedDataset", "train_streamed",
           "telemetry", "resilience", "Checkpoint", "CheckpointError",
           "TrainingPreempted", "load_checkpoint", "ModelCorruptError",
           "plot_importance", "plot_metric", "plot_tree",
           "plot_split_value_histogram", "create_tree_digraph"]
if _SKLEARN_OK:
    __all__ += ["LGBMModel", "LGBMClassifier", "LGBMRegressor", "LGBMRanker"]
