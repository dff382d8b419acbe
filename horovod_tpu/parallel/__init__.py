"""Multi-axis parallelism: dp/tp/pp/sp/ep over a hybrid mesh.

Net-new TPU capabilities beyond the dp-only reference (SURVEY §2.4):
ring/Ulysses sequence parallelism for long context, Megatron tensor
parallelism, GPipe pipeline parallelism, and GShard expert parallelism —
all as shard_map-native building blocks over `create_hybrid_mesh`.
"""

from .checkpoint import (  # noqa: F401
    restore_adapter,
    restore_sharded,
    save_adapter,
    save_sharded,
)
from .lora import (  # noqa: F401
    LoraConfig,
    adapter_bytes,
    check_adapter,
    check_adapter_name,
    init_adapter,
    stack_adapters,
)
from .kv_blocks import (  # noqa: F401
    BlockManager,
    blocks_for,
    init_paged_kv_cache,
    paged_decode_step,
    paged_kv_cache_specs,
    paged_prefill,
)
from .mesh import AXES, axis_size, create_hybrid_mesh  # noqa: F401
from .moe import moe_ffn  # noqa: F401
from .pipeline import gpipe, one_f_one_b  # noqa: F401
from .pp_transformer import (  # noqa: F401
    init_pp_params,
    make_pp_transformer_train_step,
    pp_param_specs,
)
from .ring import ring_attention, ulysses_attention  # noqa: F401
from .tp import (  # noqa: F401
    column_parallel,
    init_column,
    init_row,
    row_parallel,
)
from .transformer import (  # noqa: F401
    KimiDeltaAttention,
    LatentAttention,
    TransformerConfig,
    decode_step,
    forward,
    init_kv_cache,
    init_params,
    kv_cache_specs,
    make_parallel_train_step,
    mla_from_interleaved,
    param_specs,
    prefill,
)
