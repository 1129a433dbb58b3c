"""repro.serve — continuous-batching inference over per-user Radios.

The serving tier of the repro stack: `RequestTrace` is the
deterministic replay format (arrival cycles + per-user SNR),
`ServeEngine` runs the slot-based continuous- or static-batching
decode loop with exact Delivery billing per user — chunked bucketed
prefill for admission, and a paged shared-pool KV cache for attention
families (`PagePool` owns page allocation). See docs/ARCHITECTURE.md
§Serving and docs/ACCOUNTING.md §Serving.
"""
from repro.serve.trace import (Request, RequestTrace, make_trace,
                               uniform_trace)
from repro.serve.engine import (ServeEngine, ServeReport, RequestResult,
                                SLOT_FAMILIES, PAGED_FAMILIES,
                                SERVE_STREAM)
from repro.serve.paging import (PagePool, pages_needed, prefill_buckets,
                                bucket_for)

__all__ = [
    "Request",
    "RequestTrace",
    "make_trace",
    "uniform_trace",
    "ServeEngine",
    "ServeReport",
    "RequestResult",
    "SLOT_FAMILIES",
    "PAGED_FAMILIES",
    "SERVE_STREAM",
    "PagePool",
    "pages_needed",
    "prefill_buckets",
    "bucket_for",
]
