"""paddle_tpu.inference.serving — continuous-batching inference engine.

The serving loop over the captured ragged decode path: a paged KV-cache
pool with capacity-based admission (`kv_pool`), a scheduler that joins and
evicts requests strictly between decode steps (`scheduler`), the request
lifecycle with typed per-request TTLs (`request`), the engine that drives
prefill/decode through one whole-step-captured executable per aval
signature (`engine`), speculative decoding drafters (`speculative`:
n-gram prompt-lookup default, shrunk-model alternative) feeding the
fixed-signature [max_batch, k+1] verify step, prefix sharing over the
pool's ref-counted committed pages (`prefix`: radix tree, O(suffix)
prefill), a prefill budget (while slots decode, one prefill call a step,
a long prompt cut into pieces of the slot step: a mega-prompt can never
stall the decode batch), and the socket front-end (`gateway`:
ServingGateway + GatewayClient, typed deadlines on the wire). See README
"Serving engine" and "Serving gateway".
"""
from .engine import (  # noqa: F401
    FixedSlotStateUnsupported, SamplingUnsupported, ServingEngine,
    serving_info)
from .kv_pool import (  # noqa: F401
    KVPagePool, Page, PageUncommitted, PoolExhausted)
from .prefix import PrefixCache  # noqa: F401
from .request import Request, RequestState  # noqa: F401
from .scheduler import ContinuousBatchingScheduler  # noqa: F401
from .speculative import (  # noqa: F401
    Drafter, DraftModelDrafter, NGramDrafter, build_drafter)

__all__ = ["FixedSlotStateUnsupported", "SamplingUnsupported",
           "ServingEngine", "serving_info",
           "KVPagePool", "Page", "PageUncommitted", "PoolExhausted",
           "PrefixCache", "Request", "RequestState",
           "ContinuousBatchingScheduler", "Drafter", "NGramDrafter",
           "DraftModelDrafter", "build_drafter"]
