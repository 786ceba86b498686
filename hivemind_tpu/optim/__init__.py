from hivemind_tpu.optim.grad_averager import GradientAverager
from hivemind_tpu.optim.grad_scaler import GradScaler
from hivemind_tpu.optim.nan_guard import NaNGuard
from hivemind_tpu.optim.optimizer import Optimizer
from hivemind_tpu.optim.power_sgd_averager import PowerSGDGradientAverager
from hivemind_tpu.optim.recovery import CheckpointError, LocalCheckpointStore, restore_from_local
from hivemind_tpu.optim.progress_tracker import (
    GlobalTrainingProgress,
    LocalTrainingProgress,
    ProgressTracker,
)
from hivemind_tpu.optim.slice_optimizer import SliceOptimizer
from hivemind_tpu.optim.state_averager import TrainingStateAverager
