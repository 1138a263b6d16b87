from repro.distributed.sharding import cache_pspecs, cache_shardings, batch_axes
from repro.distributed.store import (store_pspecs, pad_store, shard_store,
                                     concat_stores)
from repro.distributed.compression import (ef_allreduce_tree, init_error_tree,
                                           quantize_int8, dequantize_int8,
                                           compression_ratio)
