"""LLM serving subsystem.

One engine and a fleet router share this package:

- :class:`PagedServingEngine` (``engine.py``) — the production path: a
  paged KV block pool with prefix caching (``block_manager.py``), a
  continuous-batching scheduler with chunked prefill, preemption,
  deadlines and load shedding (``scheduler.py``), and one jitted
  fixed-shape mixed prefill+decode step over
  ``block_multihead_attention_`` with streaming token delivery;
- :class:`ServingRouter` (``router.py``) + :class:`ReplicaHandle`
  (``replica.py``) — resilient multi-replica serving: health-checked
  circuit breakers over N identical engines, mid-stream failover with
  bit-exact replay confirmation, prefix-affinity routing, per-tenant
  weighted fair admission, graceful drain;
- :class:`DisaggRouter` (``disagg.py``) — disaggregated prefill/decode
  pools over the same replicas: lease-fenced cross-replica KV page
  migration with recompute fallback, a fleet-global prefix index, and
  an SLO autoscaler for the decode pool;
- :class:`AdapterManager` (``adapters.py``) — multi-tenant LoRA hot-swap:
  N adapter weight sets as paged, ref-counted, LRU-evictable device
  residents (stacked per-rank-class slot packs), selected per request via
  ``submit(adapter=...)``, applied segmented/gathered inside the ONE
  jitted step (mixed-adapter batches, zero steady-state retraces), with a
  CRC'd versioned manifest + store transport for fleet prefetch;
- :class:`DraftModel` (``speculative.py``) — speculative decoding: a
  small draft proposes ``k`` tokens/tick through the same paged-KV
  machinery and the existing step verifies them greedily — bit-exact
  parity with plain greedy decode, including preemption recompute and
  failover replay.

All report SLO metrics through ``observability.summary()`` (sections
``"serving"``, ``"router"``, ``"disagg"``, ``"adapters"`` and
``"spec"``).
"""
from .adapters import (ADAPTER_TARGETS, AdapterCorruptError, AdapterManager,
                       AdapterMissingError, AdapterTransport, LoraAdapter,
                       NoAdapterSlotsError, load_adapter, make_adapter,
                       pack_adapter, save_adapter, unpack_adapter)
from .block_manager import BlockManager, NoFreeBlocksError
from .disagg import (DisaggRouter, FleetPrefixIndex, MigrationError,
                     MigrationTimeout, PageCorruptError, PageTransport,
                     PoolAutoscaler, StaleEpochError, parse_pools)
from .engine import PagedServingEngine, TokenEvent
from .replica import ReplicaDeadError, ReplicaHandle, ReplicaKilledError
from .router import FailoverMismatchError, RouterRequest, ServingRouter
from .scheduler import (Completion, DeadlineExceededError, RejectedError,
                        ScheduledBatch, Scheduler, Sequence)
from .speculative import DraftModel

__all__ = [
    "AdapterManager", "LoraAdapter", "AdapterTransport",
    "AdapterMissingError", "NoAdapterSlotsError", "AdapterCorruptError",
    "ADAPTER_TARGETS", "make_adapter", "save_adapter", "load_adapter",
    "pack_adapter", "unpack_adapter",
    "DraftModel",
    "BlockManager", "NoFreeBlocksError",
    "PagedServingEngine", "TokenEvent",
    "RejectedError", "DeadlineExceededError",
    "ScheduledBatch", "Scheduler", "Sequence",
    "Completion",
    "ServingRouter", "RouterRequest", "FailoverMismatchError",
    "ReplicaHandle", "ReplicaKilledError", "ReplicaDeadError",
    "DisaggRouter", "PoolAutoscaler", "PageTransport", "FleetPrefixIndex",
    "MigrationError", "MigrationTimeout", "StaleEpochError",
    "PageCorruptError", "parse_pools",
]
