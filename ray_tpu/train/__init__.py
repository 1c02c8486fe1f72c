from ray_tpu.util import tracing as _tracing

_importing = _tracing.import_span(__name__)

from ray_tpu.train.checkpoint import CheckpointManager
from ray_tpu.train.optim8 import adamw8bit, scale_by_adam8bit
from ray_tpu.train.state import (
    TrainState,
    create_train_state,
    default_optimizer,
    state_shardings,
)
from ray_tpu.train.session import (
    TrainContext,
    get_checkpoint,
    get_context,
    report,
)
from ray_tpu.train.step import compile_train_step, make_train_step
from ray_tpu.train.trainer import (
    JaxTrainer,
    Result,
    RunConfig,
    ScalingConfig,
    TrainerConfig,
)
from ray_tpu.train import zero
from ray_tpu.train.backend import JaxBackendConfig, JaxDistributedBackend
from ray_tpu.train.worker_group import (
    BackendExecutor,
    DataParallelTrainer,
    FailureConfig,
    TrainOutput,
    WorkerGroup,
)
from ray_tpu.util import xprof as _xprof

_xprof.watch_compiles()
_importing.__exit__(None, None, None)

__all__ = [
    "BackendExecutor",
    "CheckpointManager",
    "DataParallelTrainer",
    "FailureConfig",
    "JaxBackendConfig",
    "JaxDistributedBackend",
    "JaxTrainer",
    "Result",
    "RunConfig",
    "ScalingConfig",
    "TrainContext",
    "TrainOutput",
    "TrainState",
    "TrainerConfig",
    "WorkerGroup",
    "zero",
    "compile_train_step",
    "create_train_state",
    "adamw8bit",
    "default_optimizer",
    "scale_by_adam8bit",
    "get_checkpoint",
    "get_context",
    "make_train_step",
    "report",
    "state_shardings",
]
