from .all_reduce import AllReduceParameter, padded_size, shard_batch
from .compressed import (CompressedTensor, FP16CompressedTensor,
                         FP16SplitsCompressedTensor)
from .moe import DroplessMoE, MoEFFN, aux_loss_term, collect_aux_paths
from .pipeline import (make_pipeline_eval_forward, make_pipeline_train_step,
                       pack_params, unpack_params)
from .plan import (CompiledPlanStep, Plan, Rule, compile_step_with_plan,
                   derive_plan, match_partition_rules)
from .ring_attention import (attention, blockwise_attention,
                             make_ring_attention_sharded, ring_attention,
                             ulysses_attention)
from .spmd import make_eval_forward, make_train_step, param_specs
from .tensor_parallel import ColumnParallelLinear, RowParallelLinear
