"""Combo channels (reference: SURVEY.md §2.6) — host-side composition plus
the TPU-native collective lowering."""
from .parallel_channel import (ParallelChannel, CallMapper, ResponseMerger,
                               SubCall, fanout_stats)
from .partition_channel import (PartitionChannel, DynamicPartitionChannel,
                                PartitionParser)
from .selective_channel import SelectiveChannel
from .collective_lowering import (CollectiveChannel, MERGE_SUM, MERGE_GATHER,
                                  MERGE_CONCAT, MERGE_NONE, MAP_REPLICATE,
                                  MAP_SHARD)
from .collective_fanout import (CollectiveFanoutPlane, CollectiveMerger,
                                ShardingCallMapper, ReplicateFanoutMapper,
                                fanout_reduce_stats, fanout_view_stats,
                                register_device_handler)
from .expert_dispatch import (ExpertBatch, ExpertDispatchMapper,
                              ExpertCombineMerger, expert_dispatch_stats)
