"""serve._internal — paged-KV serving internals.

Host-side machinery behind the continuous-batching engine's paged mode:
the block allocator (kv_blocks), the radix prefix cache (prefix_cache)
and the sampling-parameter plumbing (sampling). Device-side paged
attention lives in models/paged.py; these modules never import
jax — they are pure host bookkeeping that compiles block tables and
sampling plans into the i32/f32 program arguments the device programs
consume.
"""
from ray_tpu.serve._internal.kv_blocks import (  # noqa: F401
    NULL_BLOCK,
    BlockAllocator,
    BlockPoolExhausted,
)
from ray_tpu.serve._internal.prefix_cache import RadixPrefixCache  # noqa: F401
from ray_tpu.serve._internal.sampling import SamplingParams  # noqa: F401
