"""Training engine: schedules, optimizers, jitted steps, checkpointing."""

from seist_tpu.train.checkpoint import (  # noqa: F401
    PREEMPT_EXIT_CODE,
    TrainCheckpointManager,
    load_checkpoint,
    restore_into_state,
    save_checkpoint,
)
from seist_tpu.train.optim import build_optimizer, l1_sign_decay  # noqa: F401
from seist_tpu.train.schedule import (  # noqa: F401
    build_cyclic_schedule,
    cyclic_lr,
    reference_gamma,
)
from seist_tpu.train.state import TrainState, create_train_state  # noqa: F401
from seist_tpu.train.step import (  # noqa: F401
    fold_rngs,
    jit_cached_call,
    jit_device_aug_step,
    jit_eval_step,
    jit_multi_step,
    jit_step,
    make_cached_train_call,
    make_device_aug_train_step,
    make_eval_step,
    make_accum_train_step,
    make_multi_train_step,
    make_train_step,
)
