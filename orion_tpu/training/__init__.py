"""Training subsystem: trainer, data pipeline, checkpointing, metrics."""

import orion_tpu as _root
from orion_tpu.obs import trace as _trace

_trace.import_begin()  # setup.import ends at this file's last line

from orion_tpu.training.trainer import Trainer, TrainConfig
from orion_tpu.training.data import (
    SyntheticDataset,
    TokenBinDataset,
    DataLoader,
    write_token_bin,
)

_trace.import_done(__name__, _root.IMPORT_STARTED)

__all__ = [
    "Trainer",
    "TrainConfig",
    "SyntheticDataset",
    "TokenBinDataset",
    "DataLoader",
    "write_token_bin",
]
