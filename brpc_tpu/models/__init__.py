"""models/ — flagship workloads for the framework's acceptance configs.

The reference ships 25 example apps (SURVEY.md §2.8) whose heaviest
distributed workload is a parameter-server style fan-out (BASELINE.json
stretch config: "ParallelChannel parameter-server allreduce of grads").
Our flagship is a decoder-only transformer LM whose training step exercises
every mesh axis the framework defines (dp/sp/tp/ep — parallel/mesh.py):
its gradient allreduce IS the ParallelChannel lowering, its sequence
sharding IS the long-context path.
"""

from brpc_tpu.models.transformer import (  # noqa: F401
    ModelConfig,
    apply,
    init,
    param_specs,
    place_params,
)
from brpc_tpu.models.mla_moe import MlaMoeConfig  # noqa: F401
from brpc_tpu.models.decode import (  # noqa: F401
    decode_step,
    init_cache,
    prefill,
)
from brpc_tpu.models.train import (  # noqa: F401
    TrainState,
    loss_fn,
    make_train_step,
)
