from .state import TrainState, create_train_state, random_init_setup
from .schedules import build_schedule
from .optim import build_optimizer
from .step import make_eval_step

__all__ = [
    "TrainState",
    "create_train_state",
    "random_init_setup",
    "build_schedule",
    "build_optimizer",
    "make_eval_step",
]
