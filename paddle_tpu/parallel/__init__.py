"""Parallelism package: device topology, tensor/pipeline/sequence
parallelism (SURVEY §2.3 — the first-class build targets).

The reference spreads distribution across transpilers, SSA-graph passes and
NCCL op handles; here every strategy is a sharding discipline over ONE
`jax.sharding.Mesh` with named axes:

=====  =========================================================
axis   meaning
=====  =========================================================
dp     data parallel — batch dim sharded, grads psum'd
tp     tensor model parallel — param cols/rows sharded (Megatron)
pp     pipeline parallel — layer stages, ppermute microbatches
sp     sequence/context parallel — seq dim sharded, ring attention
ep     expert parallel — experts sharded, all_to_all routing
=====  =========================================================
"""

from .topology import (DeviceTopology, build_mesh, auto_mesh)  # noqa: F401
from .tp_layers import (column_parallel_fc, row_parallel_fc,  # noqa: F401
                        vocab_parallel_embedding, parallel_ffn,
                        parallel_multihead_attention)
from .ring_attention import ring_attention  # noqa: F401
from .pipeline import (gpipe_spmd, PipelineOptimizer)  # noqa: F401
from .moe import (moe_ffn, moe_dropless_ffn,  # noqa: F401
                  collect_aux_losses, apply_expert_sharding)
