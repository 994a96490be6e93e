"""Parallelism over the device mesh: the §2.9 contract and beyond.

Reference (SURVEY.md §2.9): the ONLY parallelism anywhere in the reference
was synchronous data parallelism, implemented four times (BigDL BlockManager
all-reduce, Gloo rings under torch.distributed, Horovod, TF collectives).
TPU-native collapse: one mesh, sharding annotations, XLA-compiled
collectives.  This package adds what the reference lacked and the TPU makes
natural:

- :mod:`sharding` — parameter-sharding rules (tensor parallel / FSDP) applied
  by path pattern; GSPMD propagates and inserts the collectives.
- :mod:`ring_attention` — sequence/context parallelism over the ``seq`` axis
  (shard_map + ppermute ring; SURVEY.md §5.7 'post-parity stretch').
- :mod:`moe` — mixture-of-experts layers: a capacity-based one whose experts
  shard over ``expert``, and a dropless one that holds a stated share of the
  experts (one chip's part of an expert-parallel layer).
- :mod:`pipeline` — GPipe-style pipeline parallelism over the ``pipe`` axis.
- :mod:`embedding` — device-partitioned embedding tables with deduped
  gather and sparse scatter-add gradients (the recsys sparse path).
"""

from .sharding import (ShardingRule, infer_param_specs, shard_variables,
                       tensor_parallel_rules, fsdp_rules)
from .ring_attention import ring_attention, ring_self_attention
from .moe import DroplessMoE, MoE
from .pipeline import pipeline_apply, stacked_stage_init
from .util import (GRAD_COMPRESSION, batch_shard_count, batch_shard_spec,
                   compressed_allreduce, grad_wire_bytes, quantize_int8)
from .embedding import (ShardedEmbedding, dedup_lookup, embedding_row_rules,
                        lookup_stats)

__all__ = [
    "ShardingRule", "infer_param_specs", "shard_variables",
    "tensor_parallel_rules", "fsdp_rules",
    "ring_attention", "ring_self_attention",
    "MoE", "DroplessMoE", "pipeline_apply", "stacked_stage_init",
    "GRAD_COMPRESSION", "batch_shard_count", "batch_shard_spec",
    "compressed_allreduce", "grad_wire_bytes", "quantize_int8",
    "ShardedEmbedding", "dedup_lookup", "embedding_row_rules",
    "lookup_stats",
]
