from .broadcast import (  # noqa: F401
    broadcast_optimizer_state,
    broadcast_parameters,
    broadcast_pytree,
)
from .distributed import (  # noqa: F401
    DistributedAdasumOptimizer,
    DistributedOptimizer,
    allreduce_gradients,
)
from .zero import shard_opt_state, zero1_shardings  # noqa: F401
