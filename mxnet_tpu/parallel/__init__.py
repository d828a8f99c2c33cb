"""``mxnet_tpu.parallel`` — the TPU scaling substrate.

This package is NEW capability relative to the reference (SURVEY.md §2.5):
the reference scaled via KVStore push/pull (data parallel only); here
scaling is mesh-sharded jit:

  - mesh.py:           device mesh construction (dp/tp/pp/sp axes), single- or
                       multi-host, `jax.distributed` init from DMLC_*-style env;
                       MeshConfig — the ONE named-axis dp x tp x pp config
                       (MXTPU_MESH) every hot path consumes (ISSUE 11,
                       docs/PARALLELISM.md); AXIS_DP/TP/PP constants (lint
                       HB17 bans literal copies)
  - data_parallel.py:  DataParallelTrainer — the fused jit train step with
                       in-graph grad psum over the 'dp' axis (replaces
                       kvstore push/pull on the hot path, SURVEY.md §7)
  - tensor_parallel.py: megatron-style PartitionSpec annotations for Dense/
                       Embedding/attention weights over the 'tp' axis
  - ring_attention.py: shard_map ring attention over the 'sp' axis for
                       long-context (SURVEY.md §5.7)
  - ps.py:             host-side parameter server for sparse embeddings
                       (row_sparse pull — the reference's distinctive
                       dist_async capability, §2.5 last row)
"""
from .mesh import (make_mesh, local_mesh, distributed_init, mesh_scope,
                   current_mesh, data_sharding, replicate_sharding,
                   batch_sharding, MeshConfig, mesh_config_from_env,
                   AXIS_DP, AXIS_TP, AXIS_PP)
from .data_parallel import DataParallelTrainer, all_reduce_gradients
from .overlap import OverlapScheduler
from .tensor_parallel import (shard_params_tp, tp_spec_for_param,
                              ParallelDense, ParallelEmbedding,
                              llama_tp_rules, bert_tp_rules,
                              shard_model_tp)
from .ring_attention import ring_attention, ring_attention_local, \
    sequence_parallel_attention
from .ulysses import ulysses_attention, ulysses_sequence_parallel_attention
from .pipeline_parallel import (pipeline_apply, stack_stage_params,
                                Pipeline, one_f_one_b_schedule,
                                bubble_fraction, split_into_stages,
                                PipelineStageExecutor)
from .moe import (moe_apply, MoEDense, load_balance_loss,
                  route_sigmoid_top_k, dropless_moe_apply)
from . import ps
