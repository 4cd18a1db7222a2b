"""serve — continuous-batching serving engine (slot-scheduled KV cache).

The serving tier above the GPT-family decode primitives: ONE jitted
decode step stays hot while requests are admitted and retired with no
retracing — the batch dimension of the KV cache becomes a bank of
SLOTS, each an independent request at its own length.

Three layers (docs/SERVING.md):

* ``serve.pages`` — the K/V storage and the slot cache state: a device
  page pool with per-slot page tables and start_col/write_col/positions,
  the all-slots decode step, host free-list/refcount bookkeeping, and a
  radix prefix cache that lets requests sharing a prompt prefix map the
  same read-only pages and skip those prefill windows.
* ``serve.scheduler`` — the state machine: chunked prefill (one
  fixed-width window per tick), K-step decode dispatches, EOS/budget
  retirement, slot reuse.
* ``serve.engine`` — the façade: ``submit(prompt) -> handle`` with
  streaming token callbacks, obs/ metrics (queue depth, active slots,
  TTFT and per-request decode histograms, token counters) on the
  existing ``/metrics`` endpoint.

Measured by ``bench.py --config=gpt_serve`` against a lock-step-batching
baseline in the same process; exactness (single request == greedy
``GPT.generate``, admission never perturbs other slots, kernel read ==
gather read) is pinned by tests/test_serve.py and tests/test_pages.py.
"""
from . import adapters, engine, pages, scheduler
from .adapters import AdapterTable, AdapterTableFull
from .engine import (DrainResult, Engine, QueueFullError, RequestHandle,
                     ServeMetrics)
from .pages import (PageLease, PagePool, PagePoolExhausted,
                    auto_page_size, decode_paged_step, init_paged_cache)
from .scheduler import (EngineStats, Request, RequestSnapshot,
                        SlotScheduler)

__all__ = ["AdapterTable", "AdapterTableFull", "DrainResult", "Engine",
           "EngineStats", "PageLease", "PagePool", "PagePoolExhausted",
           "QueueFullError", "RequestHandle", "RequestSnapshot",
           "ServeMetrics", "Request", "SlotScheduler", "auto_page_size",
           "decode_paged_step", "init_paged_cache",
           "adapters", "engine", "pages", "scheduler"]
