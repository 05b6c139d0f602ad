"""paddle.inference.serving — TPU-native LLM serving engine (ISSUE 7).

A real serving path for the flagship llama models: block-allocated paged
KV cache (``kv_cache``), a ragged paged-attention decode kernel with a
pure-lax CPU fallback (``paged_attention`` + ``ops/pallas``), a
continuous-batching scheduler with prefill/decode split (``scheduler``),
and the ``LLMEngine`` front-end (``engine``). The decode step keeps the
device busy: its graph returns its own argmax, so a greedy step fetches
``[B]`` int32 tokens (the ``[B, V]`` float32 rows only where a request
samples or ``capture_logits`` is on), and the next step is dispatched
before this one's tokens are fetched. See DESIGN_DECISIONS.md "Paged KV
cache & continuous batching" + "Decode dispatch-ahead" and the README
serving recipe.
"""

from .errors import (  # noqa: F401
    DeadlineInfeasibleError, EngineClosedError, FleetOverloadedError,
    KVTransferError, ReplicaCrashLoopError, RequestTimeoutError,
    TenantQuotaExceededError,
)
from .kv_cache import (  # noqa: F401
    BlockAllocator, HostKVTier, KV_QMAX, PagedKVCache, PageSnapshot,
    PrefixCache, kv_pool_bytes_per_block, pack_kv_pages,
    quantize_kv_rows, unpack_kv_pages,
)
from .prefix_store import (  # noqa: F401
    PrefixStoreMismatch, load_prefix_store, pool_geometry,
    save_prefix_store, weights_fingerprint,
)
from .scheduler import (  # noqa: F401
    Request, SamplingParams, Scheduler, TenantQuota, TIER_BATCH,
    TIER_LATENCY,
)
from .paged_attention import (  # noqa: F401
    paged_decode_attention, paged_multiquery_attention,
)
from .engine import (  # noqa: F401
    LLMEngine, StepOutput, dequantize_state_dict, is_llama_artifact,
    is_quantized_artifact, load_llama_artifact, load_llama_state_dict,
    quantize_state_dict, save_llama_artifact,
)
from . import fleet  # noqa: F401  (fleet.Router — the ISSUE-12 layer)

__all__ = [
    "BlockAllocator", "PagedKVCache", "PrefixCache", "Request",
    "SamplingParams", "Scheduler", "paged_decode_attention",
    "paged_multiquery_attention", "LLMEngine", "StepOutput",
    "save_llama_artifact", "load_llama_artifact", "is_llama_artifact",
    "is_quantized_artifact", "load_llama_state_dict",
    "quantize_state_dict", "dequantize_state_dict", "KV_QMAX",
    "quantize_kv_rows", "kv_pool_bytes_per_block", "pack_kv_pages",
    "unpack_kv_pages",
    "HostKVTier", "PageSnapshot", "PrefixStoreMismatch",
    "weights_fingerprint", "pool_geometry", "save_prefix_store",
    "load_prefix_store",
    "fleet", "RequestTimeoutError", "FleetOverloadedError",
    "EngineClosedError", "ReplicaCrashLoopError", "KVTransferError",
    "TenantQuota", "TIER_LATENCY", "TIER_BATCH",
    "TenantQuotaExceededError", "DeadlineInfeasibleError",
]
