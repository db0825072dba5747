from horovod_tpu.parallel.mesh import (  # noqa: F401
    make_mesh,
    data_parallel_mesh,
    hierarchical_mesh,
    shard_global_batch,
    MeshAxes,
)
from horovod_tpu.parallel.ring_attention import (  # noqa: F401
    ring_attention,
    ring_self_attention,
    reference_attention,
)
from horovod_tpu.parallel.zigzag_attention import (  # noqa: F401
    zigzag_ring_attention,
    zigzag_ring_self_attention,
    zigzag_shard,
    zigzag_unshard,
)
from horovod_tpu.parallel.ulysses import (  # noqa: F401
    ulysses_attention,
    ulysses_self_attention,
    seq_to_heads,
    heads_to_seq,
)
from horovod_tpu.parallel.tensor_parallel import (  # noqa: F401
    transformer_sharding_rules,
    params_shardings,
    shard_params,
    constrain,
)
from horovod_tpu.parallel.pipeline import (  # noqa: F401
    pipeline_apply,
    pipelined,
)
from horovod_tpu.parallel.moe import (  # noqa: F401
    switch_moe,
    switch_route,
    topk_moe,
    topk_route,
    init_moe_params,
)
