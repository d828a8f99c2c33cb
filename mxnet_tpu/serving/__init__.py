"""``mxnet_tpu.serving`` — compiled inference serving (ISSUE 7).

The serving vertical the ROADMAP's "millions of users" north star needs:

- :class:`InferenceEngine` — AOT-compiled prefill + single-token decode
  per power-of-two shape bucket over a paged KV cache; compile cache
  keyed and counted like the PR 1 retrace detector (zero compiles after
  warmup under traffic); optional int8 weight serving via
  ``contrib.quantization.quantize_net``.
- :class:`PagedKVCache` — block-table indexed K/V pool, per-sequence
  alloc/free, donated functional updates, per-block refcounts for
  copy-on-write prefix sharing (typed :class:`DoubleFreeError` on
  accounting violations).
- :class:`ContinuousBatcher` / :class:`StaticBatcher` — token-boundary
  continuous batching vs the fixed-batch baseline, over the same
  engine; with ``prefill_chunk`` set, admission packs chunks from
  several prompts into one dispatch (ISSUE 12).
- :mod:`frontend` — the multi-replica layer: :class:`PrefixCache`
  (system prompt prefilled once, blocks forked CoW per request) and
  :class:`Router` (least-loaded admission over N replicas, epoch-fenced
  membership, drain-and-requeue on death, one shared warmup compile
  cache).
- :class:`DraftSource` + the engine's ``verify`` graph family — ISSUE
  17 speculative decoding: model-free drafts (prefix-cache trie walk /
  prompt-lookup n-gram) scored K-at-a-time in one dispatch, greedy
  acceptance bitwise the plain decode stream; ``MXTPU_PAGED_ATTN``
  routes decode/verify attention through the Pallas paged kernel.

See docs/SERVING.md for the architecture and the bucket/compile-cache
math; ``tools/serve_loadgen.py`` is the load-generator benchmark.
"""
from __future__ import annotations

from .engine import InferenceEngine, next_bucket
from .kv_cache import PagedKVCache, DoubleFreeError, HandoffError
from .scheduler import ContinuousBatcher, Request, StaticBatcher
from .draft import DraftSource
from .frontend import PrefixCache, Router, AdmissionShed

__all__ = ["InferenceEngine", "PagedKVCache", "DoubleFreeError",
           "HandoffError", "ContinuousBatcher", "StaticBatcher",
           "Request", "next_bucket", "serving_block", "PrefixCache",
           "Router", "AdmissionShed", "DraftSource"]


def _r(x, nd=3):
    return None if x is None else round(float(x), nd)


def serving_block(max_batch=0, block_size=0, buckets=(), quantized=False,
                  continuous=True, requests=0, p50_ms=None, p99_ms=None,
                  ttft_p50_ms=None, tokens_s=None, tokens_s_chip=None,
                  occupancy=None, tokens_per_step=None,
                  compiles_after_warmup=None, cache_utilization=None,
                  chunked_prefill=False, router_replicas=0,
                  prefix_hit_rate=None, router_p99_ms=None,
                  speculative=False, paged_attn=False,
                  spec_accept_rate=None, tokens_per_dispatch=None,
                  tp_shards=0, disaggregated=False, handoff_ms=None,
                  prefill_pool_occupancy=None,
                  decode_pool_occupancy=None, kv_dtype="fp32",
                  kv_capacity_ratio=None, kv_decode_drift=None):
    """The ``serving`` summary ``tools/serve_loadgen.py`` reports (the
    `comm` block discipline from PR 3/PR 5): static serving config is
    always real;
    MEASURED fields default to ``None`` — null-when-unmeasured, so a CPU
    run can never pass off an absent measurement as "latency is zero"
    (the PR 6 honesty rule, tests/test_serving.py).  ISSUE 12 grows
    the front-end fields: ``chunked_prefill``/``router_replicas`` are
    config (always real), ``prefix_hit_rate``/``router_p99_ms`` are
    measured (null until a run actually measured them).  ISSUE 17 adds
    ``speculative``/``paged_attn`` (config) and
    ``spec_accept_rate``/``tokens_per_dispatch`` (measured).  ISSUE 18
    adds ``tp_shards``/``disaggregated`` (config) and ``handoff_ms``/
    ``prefill_pool_occupancy``/``decode_pool_occupancy`` (measured —
    null unless a disaggregated run actually measured them).  ISSUE 20
    adds ``kv_dtype`` (config: the resolved KV storage mode) and
    ``kv_capacity_ratio``/``kv_decode_drift`` (measured — the blocks
    an equal byte budget holds vs f32, and the max |logit| drift of an
    fp8-KV decode vs the f32-KV engine)."""
    return {
        "max_batch": int(max_batch),
        "block_size": int(block_size),
        "buckets": list(int(b) for b in buckets),
        "quantized": bool(quantized),
        "continuous": bool(continuous),
        "requests": int(requests),
        "p50_ms": _r(p50_ms), "p99_ms": _r(p99_ms),
        "ttft_p50_ms": _r(ttft_p50_ms),
        "tokens_s": _r(tokens_s, 1), "tokens_s_chip": _r(tokens_s_chip, 1),
        "occupancy": _r(occupancy, 4),
        "tokens_per_step": _r(tokens_per_step, 3),
        "compiles_after_warmup": (None if compiles_after_warmup is None
                                  else int(compiles_after_warmup)),
        "cache_utilization": _r(cache_utilization, 4),
        "chunked_prefill": bool(chunked_prefill),
        "router_replicas": int(router_replicas),
        "prefix_hit_rate": _r(prefix_hit_rate, 4),
        "router_p99_ms": _r(router_p99_ms),
        "speculative": bool(speculative),
        "paged_attn": bool(paged_attn),
        "spec_accept_rate": _r(spec_accept_rate, 4),
        "tokens_per_dispatch": _r(tokens_per_dispatch, 3),
        "tp_shards": int(tp_shards),
        "disaggregated": bool(disaggregated),
        "handoff_ms": _r(handoff_ms),
        "prefill_pool_occupancy": _r(prefill_pool_occupancy, 4),
        "decode_pool_occupancy": _r(decode_pool_occupancy, 4),
        "kv_dtype": str(kv_dtype or "fp32"),
        "kv_capacity_ratio": _r(kv_capacity_ratio),
        "kv_decode_drift": (None if kv_decode_drift is None
                            else float(kv_decode_drift)),
    }
