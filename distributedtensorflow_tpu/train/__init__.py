"""SPMD training engine: state, train/eval step compilation, losses."""

from .state import (TrainState, abstract_sharded_state,  # noqa: F401
                    create_sharded_state, split_variables)
from .engine import (  # noqa: F401
    accumulate_gradients,
    estimate_step_flops,
    make_eval_step,
    make_multi_train_step,
    make_train_step,
    split_microbatches,
)
from .losses import classification_eval, classification_loss  # noqa: F401
from .sidecar import SidecarEvaluator  # noqa: F401
from .trainer import Callback, Trainer, TrainerConfig, weighted_evaluate  # noqa: F401
