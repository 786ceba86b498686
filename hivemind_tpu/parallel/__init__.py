from hivemind_tpu.ops.attention import plain_attention
from hivemind_tpu.parallel.ici import MeshTensorBridge
from hivemind_tpu.parallel.mesh import (
    batch_sharding,
    make_mesh,
    param_spec,
    params_shardings,
    replicated,
)
from hivemind_tpu.parallel.ring_attention import ring_attention
