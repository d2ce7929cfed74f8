from .mesh import make_mesh, PARTS_AXIS
from .halo import halo_exchange, exchange_blocks, return_blocks, make_stale_concat
from .trainer import Trainer, TrainConfig
from .evaluator import ShardedEvaluator
from .sequential import SequentialRunner

__all__ = [
    "make_mesh",
    "PARTS_AXIS",
    "halo_exchange",
    "exchange_blocks",
    "return_blocks",
    "make_stale_concat",
    "Trainer",
    "TrainConfig",
    "ShardedEvaluator",
    "SequentialRunner",
]
