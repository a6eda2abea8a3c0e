"""chainermn_tpu — a TPU-native distributed training framework.

A ground-up rebuild of the capabilities of ChainerMN (reference:
codealphago/chainermn, a mirror of pfnet/chainermn) on the JAX/XLA stack:
device meshes + compiled collectives over ICI/DCN instead of MPI + NCCL,
functional transforms instead of define-by-run hooks, and `pjit`/`shard_map`
SPMD instead of an mpiexec process-per-GPU model.

Public surface mirrors the reference's top level
(chainermn/__init__.py per SURVEY.md §2.5; reference mount was empty):
``create_communicator``, ``create_multi_node_optimizer``, ``scatter_dataset``,
``functions``, ``links``, the multi-node iterator/evaluator/checkpointer
factories, and the global exception hook.
"""

from chainermn_tpu.comm import (
    CommunicatorBase,
    XlaCommunicator,
    create_communicator,
)
from chainermn_tpu import collectives, functions, links
from chainermn_tpu.collectives import make_grad_reducer
from chainermn_tpu.datasets import (
    create_empty_dataset,
    scatter_dataset,
)
from chainermn_tpu.extensions import (
    create_multi_node_checkpointer,
    create_multi_node_evaluator,
    install_global_except_hook,
)
from chainermn_tpu.iterators import (
    create_multi_node_iterator,
    create_synchronized_iterator,
)
from chainermn_tpu.links import MultiNodeBatchNormalization, MultiNodeChainList
from chainermn_tpu.optimizers import create_multi_node_optimizer
from chainermn_tpu import checkpointing
from chainermn_tpu import fleet
from chainermn_tpu import resilience
from chainermn_tpu import serving

__version__ = "0.1.0"

__all__ = [
    "CommunicatorBase",
    "XlaCommunicator",
    "create_communicator",
    "create_multi_node_optimizer",
    "collectives",
    "make_grad_reducer",
    "scatter_dataset",
    "create_empty_dataset",
    "create_multi_node_iterator",
    "create_synchronized_iterator",
    "create_multi_node_evaluator",
    "create_multi_node_checkpointer",
    "install_global_except_hook",
    "functions",
    "links",
    "MultiNodeBatchNormalization",
    "MultiNodeChainList",
    "checkpointing",
    "fleet",
    "resilience",
    "serving",
    "__version__",
]
