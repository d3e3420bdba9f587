"""The serving subsystem: transport, engines, and the paged KV cache.

Grown out of the r07 `serving.py`/`serving_engine.py` pair into a
package (ISSUE r20 tentpole):

- `transport`  — the request/response RPC layer (`PredictorServer` /
  `PredictorClient`, v2 vectored framing). The old `paddle_tpu.serving`
  module surface — every public name is re-exported here, so existing
  imports keep working.
- `engine`     — the continuous-batching generation engine over the
  slot-indexed KV cache (`ContinuousBatchingEngine`, `EngineServer`,
  `EngineClient`). The old `paddle_tpu.serving_engine` module (a compat
  shim remains at that path).
- `kv_pager`   — the paged KV-cache subsystem: a device-resident block
  pool of fixed `block_size`-token pages, per-request block tables, a
  free-list allocator with LRU eviction of cached prefix blocks, a
  prefix-sharing radix index with copy-on-write at the divergence
  block, and `PagedKVEngine` — the engine that decodes through it
  (token-identical to the slot engine, at a fraction of the KV bytes
  per request; tests/test_kv_pager.py).
- `sanitizer`  — the shadow-state sanitizer over the paged KV stack
  (r24): with the `kv_sanitize` flag on (`PTPU_KV_SANITIZE=1`), every
  `KVPager` mirrors its block-lifetime mutations against the abstract
  ownership model (`framework/ownership.py`) and raises
  `SanitizerDivergence` naming op/block/invariant on the first drift.
- `speculative` — speculative decoding over either engine
  (`SpecConfig`, `SpeculativeDecoder`): a quantized draft twin proposes
  γ tokens, one γ+1-wide target forward verifies, rejected paged blocks
  roll back through the pager (greedy mode token-identical to plain
  decode; tests/test_speculative.py).
"""

from __future__ import annotations

# -- transport: the full old `paddle_tpu.serving` surface ------------------
from .transport import (  # noqa: F401
    PredictorClient,
    PredictorServer,
    _BatchingWriter,
    _RecvBufferPool,
    _byte_views,
    _encode_msg,
    _recv_exact,
    _recv_exact_into,
    _recv_msg,
    _send_msg,
    _sendall_vec,
)

# -- engine ----------------------------------------------------------------
from .engine import (  # noqa: F401
    ContinuousBatchingEngine,
    EngineClient,
    EngineServer,
    GenRequest,
    SlotAllocator,
    scrape_healthz,
    scrape_metrics,
)

# -- speculative decoding --------------------------------------------------
from .speculative import (  # noqa: F401
    SpecConfig,
    SpeculativeDecoder,
    rejection_sample,
)

# -- paged KV cache --------------------------------------------------------
from .kv_pager import (  # noqa: F401
    BlockPool,
    BlockTable,
    HostTierConfig,
    KVPager,
    PagedKVEngine,
    RadixPrefixIndex,
    paged_beam_search,
)

# -- shadow-state sanitizer (r24) ------------------------------------------
from .sanitizer import (  # noqa: F401
    KVSanitizer,
    SanitizerDivergence,
)
