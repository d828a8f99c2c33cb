#!/usr/bin/env python
"""Serving load generator: continuous vs static batching on the compiled
inference engine (mxnet_tpu.serving), with p50/p99 latency, tokens/s,
and batch occupancy — the ISSUE 7 serving benchmark.

The request mix is DETERMINISTIC (prompt lengths and generation budgets
cycle through fixed lists), so the policy comparison — tokens-per-step
and occupancy — is exact and CI-gateable; walltime-derived numbers
(tokens/s, p50/p99) ride along as evidence, never as gates.

Usage:
  python tools/serve_loadgen.py --smoke           # CPU-sized, tier-1
  python tools/serve_loadgen.py --requests 64 --max-batch 8
  python tools/serve_loadgen.py --mode continuous|static|both
  python tools/serve_loadgen.py --smoke --replicas 2   # router fleet:
      shared-system-prompt mix through N replicas (prefix cache +
      chunked prefill on), reporting prefix hit rate and per-replica
      occupancy (ISSUE 12)
  python tools/serve_loadgen.py --smoke --speculative  # draft/verify
      decoding on the continuous policy (outputs bitwise unchanged;
      reports acceptance rate + tokens per dispatch, ISSUE 17)
  python tools/serve_loadgen.py --smoke --disagg --replicas 4  # split
      the fleet into prefill/decode pools over one shared KV pool,
      reporting handoffs + per-pool occupancy (ISSUE 18)
  python tools/serve_loadgen.py --smoke --replicas 2 --tp 2  # shard
      every replica's weights + KV pool on a tp submesh (ISSUE 18;
      outputs bitwise unchanged)
  python tools/serve_loadgen.py --smoke --kv-dtype fp8  # store the
      paged KV pool in fp8 with per-row amax scales (ISSUE 20):
      reports kv_capacity_ratio (blocks an equal byte budget holds vs
      f32 — pure pool arithmetic, real on CPU) and kv_decode_drift
      (max |logit| gap of a short greedy decode vs an explicit
      fp32-KV engine on the same weights)
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

# smoke mix: mixed prompt lengths + mixed generation budgets — the
# shape of traffic where continuous batching wins (short requests vacate
# slots that static batching would leave idle)
_PROMPT_MIX = (5, 12, 24, 8, 17, 3)
_NEW_MIX = (4, 12, 6, 16, 3, 9)
# router mix: every request opens with the SAME system prompt (the
# millions-of-users shape) — deterministic, so the prefix hit rate and
# the computed-token savings are exact, CI-gateable quantities
_SYS_PROMPT_LEN = 12
_USER_MIX = (5, 9, 3, 7, 4, 11)


def _build_net(smoke):
    import numpy as np
    import mxnet_tpu as mx
    from mxnet_tpu.gluon.model_zoo.nlp.llama import (LlamaConfig,
                                                     LlamaForCausalLM)
    if smoke:
        cfg = LlamaConfig(vocab_size=128, hidden_size=64, num_layers=2,
                          num_heads=4, num_kv_heads=2,
                          intermediate_size=128, max_seq_len=128,
                          tie_embeddings=True)
    else:
        cfg = LlamaConfig(vocab_size=32000, hidden_size=1024,
                          num_layers=8, num_heads=16, num_kv_heads=8,
                          intermediate_size=2816, max_seq_len=1024)
    net = LlamaForCausalLM(cfg)
    net.initialize()
    net(mx.nd.array(np.zeros((1, 4), np.int32)))   # materialize shapes
    net.hybridize()
    return net, cfg


def _requests(n, vocab, seed=0):
    import numpy as np
    from mxnet_tpu.serving import Request
    rng = np.random.RandomState(seed)
    out = []
    for i in range(n):
        t = _PROMPT_MIX[i % len(_PROMPT_MIX)]
        new = _NEW_MIX[i % len(_NEW_MIX)]
        out.append(Request(rng.randint(0, vocab, (t,)).tolist(), new))
    return out


def _kv_capacity_ratio(cfg, kv_dtype, block_size):
    """Blocks an equal byte budget holds under ``kv_dtype`` vs f32 —
    pure pool arithmetic (ISSUE 20), so it is REAL on a CPU run.  The
    budget is what 256 f32 blocks of this model's KV geometry cost;
    fp8 pays its per-row f32 scale planes out of the same budget."""
    from mxnet_tpu.ops.quant_kv import kv_block_bytes, kv_blocks_in_budget
    if kv_dtype is None:
        return None
    hd = cfg.hidden_size // cfg.num_heads
    geom = dict(num_layers=cfg.num_layers, num_kv_heads=cfg.num_kv_heads,
                head_dim=hd, block_size=block_size)
    budget = 256 * kv_block_bytes(**geom)
    f32 = kv_blocks_in_budget(budget, **geom)
    lowp = kv_blocks_in_budget(budget, kv_dtype=kv_dtype, **geom)
    return round(lowp / f32, 3) if f32 else None


def _kv_decode_drift(net, cfg, kv_dtype, block_size, max_context, seed):
    """Max |logit| drift of a short greedy decode under the
    low-precision KV store vs an explicit fp32-KV engine on the SAME
    weights and prompt — the ISSUE 20 serving drift evidence.  Two
    tiny single-slot engines; measured only when --kv-dtype asks."""
    import numpy as np
    from mxnet_tpu.serving import InferenceEngine
    rng = np.random.RandomState(seed + 7)
    prompt = rng.randint(0, cfg.vocab_size, (9,)).tolist()
    per_mode = []
    for kd in ("fp32", kv_dtype):
        eng = InferenceEngine(net, max_batch=1, block_size=block_size,
                              max_context=max_context, kv_dtype=kd)
        tok, _ = eng.prefill(0, prompt)
        cur = list(prompt) + [int(tok)]
        rows = []
        for _ in range(4):
            pos = len(cur) - 1
            assert eng.reserve(0, pos)
            nxt, lg = eng.decode([(0, cur[-1], pos)])
            rows.append(np.asarray(lg[0], np.float32))
            cur.append(int(nxt[0]))
        eng.release(0)
        per_mode.append(rows)
    return max(float(np.max(np.abs(a - b)))
               for a, b in zip(*per_mode))


def run_router_loadgen(n_requests=12, max_batch=4, block_size=8,
                       max_context=64, smoke=True, replicas=2, seed=0,
                       disaggregated=False, tp=0, kv_dtype=None):
    """The ISSUE 12 fleet benchmark: a deterministic shared-system-
    prompt mix through ``replicas`` engine replicas behind one Router
    (prefix cache + chunked prefill on, shared warmup compile cache,
    deterministic drive).  Returns the bench `serving` payload with the
    front-end fields measured: prefix hit rate, per-replica occupancy,
    router p50/p99.  ISSUE 18: ``disaggregated`` splits the fleet into
    prefill/decode pools over ONE shared KV pool (paged-block handoff);
    ``tp > 1`` shards every replica's weights + KV pool on a tp submesh
    (outputs bitwise unchanged either way — the benchmark measures the
    placement, not the math).  ISSUE 20: ``kv_dtype="fp8"`` stores
    every replica's KV pool quantized (capacity + drift reported)."""
    import numpy as np
    from mxnet_tpu import telemetry
    from mxnet_tpu.ops.quant_kv import resolve_kv_dtype
    from mxnet_tpu.serving import InferenceEngine, Request, Router, \
        serving_block
    kv_dtype = resolve_kv_dtype(kv_dtype)
    mesh = None
    if tp and tp > 1:
        from mxnet_tpu.parallel import MeshConfig
        mesh = MeshConfig(tp=tp)
    net, cfg = _build_net(smoke)
    rng = np.random.RandomState(seed)
    sys_prompt = rng.randint(0, cfg.vocab_size,
                             (_SYS_PROMPT_LEN,)).tolist()

    # a disaggregated fleet shares ONE pool across every replica's
    # slots (plus the prefix pins), so the creator sizes it fleet-wide;
    # per-replica pools keep the engine's own default
    num_blocks = (1 + replicas * (max_batch + 1)
                  * (max_context // block_size)
                  if disaggregated else None)

    def factory(compile_cache, kv_cache=None):
        return InferenceEngine(net, max_batch=max_batch,
                               block_size=block_size,
                               max_context=max_context,
                               num_blocks=num_blocks,
                               prefill_chunk=2 * block_size,
                               prefix_cache=True, mesh=mesh,
                               compile_cache=compile_cache,
                               kv_cache=kv_cache,
                               kv_dtype=kv_dtype or "fp32")

    router = Router(factory, replicas=replicas,
                    disaggregated=disaggregated)
    for rep in router.replicas:
        if rep.role != "decode":   # decode-role replicas never prefill
            rep.engine.pin_prefix(sys_prompt)
    reqs = []
    for i in range(n_requests):
        user = rng.randint(0, cfg.vocab_size,
                           (_USER_MIX[i % len(_USER_MIX)],)).tolist()
        reqs.append(Request(sys_prompt + user,
                            _NEW_MIX[i % len(_NEW_MIX)]))
    t0 = time.perf_counter()
    for req in reqs:
        router.submit(req)
    router.drive()
    wall = time.perf_counter() - t0
    st = router.stats()
    tokens = sum(len(r.generated) for r in router.finished())
    prefix_hits = 0
    prefix_lookups = 0
    hit_tokens = 0
    computed = 0
    for rep in router.replicas:
        pc = rep.engine.prefix_cache
        prefix_hits += pc.hits
        prefix_lookups += pc.lookups
        hit_tokens += pc.hit_tokens
        computed += rep.engine.stats["prompt_tokens_computed"]
    hit_rate = prefix_hits / prefix_lookups if prefix_lookups else None
    drift = (None if kv_dtype is None else
             _kv_decode_drift(net, cfg, kv_dtype, block_size,
                              max_context, seed))
    blk = serving_block(
        max_batch=max_batch, block_size=block_size,
        buckets=_buckets(block_size, max_context),
        continuous=True, requests=st["requests"],
        p50_ms=_ms(st["p50_latency_s"]), p99_ms=_ms(st["p99_latency_s"]),
        tokens_s=(round(tokens / wall, 1) if wall > 0 else None),
        tokens_s_chip=(round(tokens / wall / replicas, 1)
                       if wall > 0 else None),
        occupancy=(sum(o) / len(o) if (o := [
            r["occupancy"] for r in st["per_replica"]
            if r["occupancy"] is not None]) else None),
        compiles_after_warmup=st["compiles_after_warmup"],
        chunked_prefill=True, router_replicas=replicas,
        prefix_hit_rate=hit_rate, router_p99_ms=_ms(st["p99_latency_s"]),
        tp_shards=(tp if tp and tp > 1 else 0),
        disaggregated=bool(st.get("disaggregated")),
        handoff_ms=(telemetry.value("serving.handoff_ms")
                    if telemetry.enabled() else None),
        prefill_pool_occupancy=st.get("prefill_pool_occupancy"),
        decode_pool_occupancy=st.get("decode_pool_occupancy"),
        kv_dtype=kv_dtype or "fp32",
        kv_capacity_ratio=_kv_capacity_ratio(cfg, kv_dtype, block_size),
        kv_decode_drift=drift)
    return {"metric": "serve_loadgen", "mode": "router",
            "smoke": bool(smoke), "serving": blk,
            "router": {
                "epoch": st["epoch"], "requeues": st["requeues"],
                "handoffs": st.get("handoffs", 0),
                "prompt_tokens_computed": computed,
                "prefix_hit_tokens": hit_tokens,
                "warmup_compiles_shared":
                    router.warmup_compiles_shared,
                "per_replica": [
                    {"rid": r["rid"], "role": r.get("role"),
                     "requests": r["requests"],
                     "occupancy": r["occupancy"]}
                    for r in st["per_replica"]],
            }}


def run_loadgen(n_requests=12, max_batch=4, block_size=8, max_context=64,
                mode="both", smoke=True, quantize=None, seed=0,
                replicas=0, speculative=False, disaggregated=False,
                tp=0, kv_dtype=None):
    """Run the mix through the chosen scheduling policy(ies); returns
    the bench `serving` payload.  ``replicas >= 1`` switches to the
    router fleet benchmark (:func:`run_router_loadgen`).
    ``speculative`` turns on draft/verify decoding for the CONTINUOUS
    policy (greedy acceptance is bitwise, so the comparison still
    measures scheduling, now in tokens-per-dispatch).
    ``disaggregated``/``tp`` are the ISSUE 18 fleet shapes (router
    benchmark only; ``disaggregated`` implies ``replicas >= 2``).
    ``kv_dtype`` (ISSUE 20) stores the paged KV pool quantized
    (``"fp8"``/``"bf16"``): the payload gains ``kv_capacity_ratio``
    (equal-byte-budget blocks vs f32) and ``kv_decode_drift`` (max
    |logit| gap vs an explicit fp32-KV engine)."""
    from mxnet_tpu import telemetry
    from mxnet_tpu.ops.quant_kv import resolve_kv_dtype
    from mxnet_tpu.serving import (ContinuousBatcher, InferenceEngine,
                                   StaticBatcher, serving_block)
    kv_dtype = resolve_kv_dtype(kv_dtype)
    if disaggregated and replicas < 2:
        replicas = 2
    if replicas:
        return run_router_loadgen(
            n_requests=n_requests, max_batch=max_batch,
            block_size=block_size, max_context=max_context,
            smoke=smoke, replicas=replicas, seed=seed,
            disaggregated=disaggregated, tp=tp, kv_dtype=kv_dtype)
    mesh = None
    if tp and tp > 1:
        from mxnet_tpu.parallel import MeshConfig
        mesh = MeshConfig(tp=tp)
    results = {}
    paged = False
    for policy in (("continuous", "static") if mode == "both"
                   else (mode,)):
        net, cfg = _build_net(smoke)
        kw = {}
        if quantize:
            import numpy as np
            import mxnet_tpu as mx
            rng = np.random.RandomState(seed)
            kw = {"quantize": quantize,
                  "calib_data": [mx.nd.array(
                      rng.randint(0, cfg.vocab_size, (2, 16)),
                      dtype="int32") for _ in range(2)]}
        # the static baseline never drafts (its decode loop is the
        # policy under comparison), so its engine skips the verify
        # graph compiles
        engine = InferenceEngine(net, max_batch=max_batch,
                                 block_size=block_size,
                                 max_context=max_context, mesh=mesh,
                                 spec_decode=(speculative and
                                              policy == "continuous"),
                                 kv_dtype=kv_dtype or "fp32", **kw)
        paged = engine.paged_attn
        engine.warmup()
        cls = (ContinuousBatcher if policy == "continuous"
               else StaticBatcher)
        # priming pass: the first requests through a process also pay
        # one-time host-side jit warmups (key folding, conversions);
        # keep those out of the measured window so the policy
        # comparison is apples-to-apples
        prime = cls(engine)
        for req in _requests(2, cfg.vocab_size, seed + 1):
            prime.submit(req)
        prime.run()
        batcher = cls(engine)
        for req in _requests(n_requests, cfg.vocab_size, seed):
            batcher.submit(req)
        # ISSUE 9 thin-reader discipline: the measured window's compile
        # count comes off the PROCESS telemetry registry (the same
        # source a live scrape sees) as a before/after delta — the
        # registry outlives the two per-policy engines this function
        # builds.  Engine-local stats remain the fallback when the
        # telemetry kill switch is on.
        caw0 = telemetry.value("serving.compiles_after_warmup")
        t0 = time.perf_counter()
        stats = batcher.run()
        wall = time.perf_counter() - t0
        stats["wall_s"] = round(wall, 3)
        stats["tokens_s"] = round(stats["tokens_generated"] / wall, 1) \
            if wall > 0 else None
        stats["tokens_per_step"] = round(
            stats["tokens_generated"] / stats["decode_steps"], 3) \
            if stats["decode_steps"] else None
        if telemetry.enabled():
            caw1 = telemetry.value("serving.compiles_after_warmup")
            stats["compiles_after_warmup"] = (caw1 or 0) - (caw0 or 0)
            stats["cache_utilization"] = telemetry.value(
                "serving.kv_block_utilization")
        else:
            stats["compiles_after_warmup"] = \
                engine.stats["compiles_after_warmup"]
            stats["cache_utilization"] = None
        stats["ttfts"] = sorted(
            round(r.ttft(), 4) for r in batcher.finished
            if r.ttft() is not None)
        results[policy] = stats
    cont = results.get("continuous") or next(iter(results.values()))
    drift = (None if kv_dtype is None else
             _kv_decode_drift(net, cfg, kv_dtype, block_size,
                              max_context, seed))
    blk = serving_block(
        max_batch=max_batch, block_size=block_size,
        buckets=_buckets(block_size, max_context),
        quantized=bool(quantize), continuous="continuous" in results,
        requests=cont["requests"],
        p50_ms=_ms(cont.get("p50_latency_s")),
        p99_ms=_ms(cont.get("p99_latency_s")),
        ttft_p50_ms=_ms(cont["ttfts"][len(cont["ttfts"]) // 2]
                        if cont.get("ttfts") else None),
        tokens_s=cont.get("tokens_s"),
        tokens_s_chip=cont.get("tokens_s"),   # single chip here
        occupancy=cont.get("occupancy"),
        tokens_per_step=cont.get("tokens_per_step"),
        compiles_after_warmup=cont.get("compiles_after_warmup"),
        cache_utilization=cont.get("cache_utilization"),
        speculative=bool(speculative), paged_attn=paged,
        spec_accept_rate=cont.get("spec_accept_rate"),
        tokens_per_dispatch=cont.get("tokens_per_dispatch"),
        tp_shards=(tp if tp and tp > 1 else 0),
        kv_dtype=kv_dtype or "fp32",
        kv_capacity_ratio=_kv_capacity_ratio(cfg, kv_dtype, block_size),
        kv_decode_drift=drift)
    payload = {"metric": "serve_loadgen", "mode": mode,
               "smoke": bool(smoke), "serving": blk,
               "policies": {k: {kk: vv for kk, vv in v.items()
                                if kk != "ttfts"}
                            for k, v in results.items()}}
    if mode == "both":
        c, s = results["continuous"], results["static"]
        payload["continuous_vs_static"] = {
            "tokens_per_step_ratio": round(
                c["tokens_per_step"] / s["tokens_per_step"], 3)
            if s.get("tokens_per_step") else None,
            "occupancy_ratio": round(c["occupancy"] / s["occupancy"], 3)
            if s.get("occupancy") else None,
            "decode_steps": {"continuous": c["decode_steps"],
                             "static": s["decode_steps"]},
        }
    return payload


def _buckets(bs, mc):
    out = []
    b = bs
    while b <= mc:
        out.append(b)
        b *= 2
    return out


def _ms(s):
    return None if s is None else s * 1e3


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--smoke", action="store_true",
                    help="tiny CPU-sized model + short mix (tier-1)")
    ap.add_argument("--requests", type=int, default=None)
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--block-size", type=int, default=None)
    ap.add_argument("--max-context", type=int, default=None)
    ap.add_argument("--mode", choices=("continuous", "static", "both"),
                    default="both")
    ap.add_argument("--int8", action="store_true",
                    help="serve int8-quantized weights")
    ap.add_argument("--replicas", type=int, default=0,
                    help="N>=1: router fleet benchmark with a shared-"
                         "system-prompt mix (prefix cache + chunked "
                         "prefill); 0 = single-engine policy comparison")
    ap.add_argument("--speculative", action="store_true",
                    help="draft/verify decoding on the continuous "
                         "policy (greedy outputs unchanged; reports "
                         "acceptance rate + tokens per dispatch)")
    ap.add_argument("--disagg", action="store_true",
                    help="disaggregated prefill/decode fleet: split "
                         "the router replicas into prefill and decode "
                         "pools over ONE shared KV pool (paged-block "
                         "handoff; implies --replicas >= 2)")
    ap.add_argument("--tp", type=int, default=0,
                    help="N>1: shard weights + KV pool on a tp=N "
                         "submesh (outputs bitwise unchanged)")
    ap.add_argument("--kv-dtype", choices=("fp32", "bf16", "fp8"),
                    default=None,
                    help="KV-cache storage precision (ISSUE 20): fp8 "
                         "stores per-row amax-scaled codes and reports "
                         "kv_capacity_ratio (equal-byte blocks vs f32) "
                         "+ kv_decode_drift (max |logit| gap vs an "
                         "fp32-KV engine); default follows "
                         "MXTPU_KV_DTYPE")
    args = ap.parse_args(argv)
    smoke = args.smoke
    if args.tp and args.tp > 1 and smoke:
        # standalone smoke runs need the simulated device mesh; must be
        # set before the first jax import (all imports here are lazy)
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=8"
            ).strip()
    n = args.requests if args.requests is not None else (12 if smoke
                                                         else 64)
    from mxnet_tpu.runtime import enable_compile_cache
    enable_compile_cache()
    payload = run_loadgen(
        n_requests=n, max_batch=args.max_batch,
        block_size=args.block_size or (8 if smoke else 16),
        max_context=args.max_context or (64 if smoke else 512),
        mode=args.mode, smoke=smoke,
        quantize="int8" if args.int8 else None,
        replicas=args.replicas, speculative=args.speculative,
        disaggregated=args.disagg, tp=args.tp,
        kv_dtype=args.kv_dtype)
    out = json.dumps(payload)
    if len(out) > 1800:      # the driver tail-window contract
        slim = dict(payload)
        slim.pop("policies", None)
        out = json.dumps(slim)
    print(out, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
