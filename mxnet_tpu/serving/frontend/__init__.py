"""``mxnet_tpu.serving.frontend`` — the multi-replica serving front end
(ISSUE 12).

Three pieces turn PR 7's single-replica engine into a servable fleet:

- :class:`PrefixCache` — hashes token prefixes to KV block chains so a
  system prompt shared by every request is prefilled ONCE; per-request
  blocks fork copy-on-write (``PagedKVCache`` refcounts), and LRU
  eviction only ever reclaims chains no live request still reads.
- chunked/batched prefill — the engine's ``chunk`` graph family plus
  ``ContinuousBatcher``'s packed admission: several queued prompts (and
  the tail chunks of long ones) ride ONE prefill dispatch per boundary.
- :class:`Router` — N engine replicas behind least-loaded admission on
  the PR 9 registry signals, an epoch-numbered replica set, death ->
  drain -> requeue with zero lost or duplicated requests, and one
  shared warmup compile cache for the whole fleet.

See docs/SERVING.md §Front-end; the chaos gate is
``python -m mxnet_tpu.testing.chaos serving``.
"""
from __future__ import annotations

from .prefix_cache import PrefixCache
from .router import Router, Replica, AdmissionShed

__all__ = ["PrefixCache", "Router", "Replica", "AdmissionShed"]
