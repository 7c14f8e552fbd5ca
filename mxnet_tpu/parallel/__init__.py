"""Distributed training over device meshes (see SURVEY.md §3.5)."""
from .mesh import (make_mesh, named_sharding, replicated, use_mesh,  # noqa: F401
                   current_mesh, shard_array, shard_map, P, AXES)
from .data_parallel import (build_train_step, tree_optimizer_step,  # noqa: F401
                            replicate_params, shard_batch, block_loss_fn,
                            weight_update_spec)
from . import tensor_parallel  # noqa: F401
from .tensor_parallel import (shard_params, param_specs, constrain,  # noqa: F401
                              psum_region_entry, psum_region_exit)
from .ring_attention import ring_attention, full_attention  # noqa: F401
from .ulysses import ulysses_attention  # noqa: F401
from .pipeline import (pipeline_apply, pipeline_apply_interleaved,  # noqa: F401
                       pipeline_train_step_1f1b, stack_stage_params,
                       interleave_stage_params)
from .expert_parallel import moe_ffn  # noqa: F401
from ..ops.attention import sequence_parallel_scope  # noqa: F401
from .resilience import Heartbeat, ResumableLoop  # noqa: F401
from . import distributed  # noqa: F401
from .distributed import init_process_group, global_mesh  # noqa: F401
