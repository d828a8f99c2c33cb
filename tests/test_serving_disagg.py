"""Disaggregated prefill/decode serving (ISSUE 18, tentpole B).

One shared ``PagedKVCache`` behind PREFILL-role and DECODE-role
replicas: a prefill replica fills a request's blocks, then OWNERSHIP
moves to a decode replica through the pool's CoW refcounts —
adopt-then-release, so a crash between the two sides strands nothing
and duplicates nothing (typed :class:`HandoffError` on every protocol
violation).  The acceptance bar is BITWISE: the disaggregated fleet
must produce exactly the token streams of a solo combined-role
replica, with zero compiles after warmup and a leak-clean shared pool.

Runs on the simulated 8-device CPU mesh (tests/conftest.py).
"""
from __future__ import annotations

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu.base import MXNetError, NotSupportedError
from mxnet_tpu.gluon.model_zoo.nlp.llama import (LlamaConfig,
                                                 LlamaForCausalLM)
from mxnet_tpu.serving import (ContinuousBatcher, HandoffError,
                               InferenceEngine, Request, Router)

_STATE = {}


def _net():
    if "net" not in _STATE:
        cfg = LlamaConfig(vocab_size=64, hidden_size=32, num_layers=2,
                          num_heads=4, num_kv_heads=2,
                          intermediate_size=64, max_seq_len=64,
                          tie_embeddings=True)
        net = LlamaForCausalLM(cfg)
        net.initialize()
        net(mx.nd.array(np.zeros((1, 8), np.int32)))
        net.hybridize()
        _STATE["net"] = net
    return _STATE["net"]


# ONE compile cache for the whole module: every router/solo engine
# below shares it (signatures key on config + mesh, so layouts never
# collide), which keeps the file's compile bill to one warmup per
# distinct graph family
_CC = {}


def _factory(compile_cache, kv_cache=None, **kw):
    base = dict(max_batch=2, block_size=8, num_blocks=32,
                max_context=32)
    base.update(kw)
    return InferenceEngine(_net(), compile_cache=_CC,
                           kv_cache=kv_cache, **base)


def _prompts(n, seed=0):
    rng = np.random.RandomState(seed)
    return [list(rng.randint(0, 64, (3 + i % 5,))) for i in range(n)]


def _solo_streams(prompts, **kw):
    """The combined-role reference streams, one solo batcher."""
    solo = ContinuousBatcher(_factory({}, **kw).warmup())
    reqs = [solo.submit(Request(list(p), max_new_tokens=4))
            for p in prompts]
    solo.run()
    return [list(r.generated) for r in reqs]


def _fleet():
    """One 2-replica disaggregated run, shared across the read-only
    assertions below (the fleet is deterministic: build once)."""
    if "fleet" not in _STATE:
        prompts = _prompts(7)
        refs = _solo_streams(prompts)
        router = Router(_factory, replicas=2, disaggregated=True)
        reqs = [Request(list(p), max_new_tokens=4) for p in prompts]
        for r in reqs:
            router.submit(r)
        router.drive()
        _STATE["fleet"] = (router, reqs, refs)
    return _STATE["fleet"]


def test_disagg_outputs_bitwise_solo_and_leak_clean():
    router, reqs, refs = _fleet()
    assert [list(r.generated) for r in reqs] == refs
    st = router.stats()
    assert st["disaggregated"] is True
    assert st["handoffs"] == len(reqs)   # every request crossed over
    assert st["requeues"] == 0
    assert st["compiles_after_warmup"] == 0
    # every slot released on both sides: the shared pool is empty
    router._shared_cache.check_leaks(holders=0)


def test_disagg_roles_and_shared_pool_in_manifest():
    router, _reqs, _refs = _fleet()
    man = router.manifest()
    assert man["disaggregated"] is True
    roles = {r["rid"]: r["role"] for r in man["replicas"]}
    assert roles == {0: "prefill", 1: "decode"}
    assert all(r["cache_shared"] for r in man["replicas"])
    # ONE pool object behind every replica
    caches = {id(rep.engine.cache) for rep in router.replicas}
    assert len(caches) == 1


def test_disagg_per_pool_occupancy_measured():
    router, _reqs, _refs = _fleet()
    st = router.stats()
    assert 0.0 < st["prefill_pool_occupancy"] <= 1.0
    assert 0.0 < st["decode_pool_occupancy"] <= 1.0
    roles = {r["rid"]: r["role"] for r in router.manifest()["replicas"]}
    for pr in st["per_replica"]:
        assert pr["role"] == roles[pr["rid"]]


def test_disagg_decode_replicas_never_admit():
    router, _reqs, _refs = _fleet()
    # submits landed only on the prefill replica; handoffs moved them
    assert all(rep.role == "prefill" or not rep.batcher.queue
               for rep in router.replicas)
    prefill_rep = router.replicas[0]
    decode_rep = router.replicas[1]
    assert len(decode_rep.batcher.finished) == 7
    assert not prefill_rep.batcher.handoff_ready


def test_disagg_threaded_start_typed_rejection():
    router, _reqs, _refs = _fleet()
    with pytest.raises(NotSupportedError):
        router.start()


def test_handoff_protocol_violations_are_typed():
    """Every way to break adopt-then-release raises HandoffError."""
    eng = _factory({}).warmup()
    # adopt on a non-decode role
    b = ContinuousBatcher(eng, role="combined")
    with pytest.raises(HandoffError):
        b.adopt_handoff(Request([1, 2], 2), [0], 2)
    # release-before-adopt: the prefill side may not drop its hold
    # until the decode side holds every block (refcount >= 2)
    pre = ContinuousBatcher(eng, slot_ns=0, role="prefill")
    req = pre.submit(Request([1, 2, 3], max_new_tokens=4))
    pre.step()
    assert pre.handoff_ready
    slot, _req = pre.handoff_ready[0]
    with pytest.raises(HandoffError):
        pre.complete_handoff(slot)
    eng.release(slot)
    pre.handoff_ready.clear()
    eng.cache.check_leaks(holders=0)


def test_disagg_factory_must_share_pool():
    """An engine_factory that ignores its kv_cache argument builds
    per-replica pools — the handoff protocol is impossible; typed
    rejection at construction."""
    def bad_factory(compile_cache, kv_cache=None):
        return _factory(compile_cache, kv_cache=None)
    with pytest.raises(HandoffError):
        Router(bad_factory, replicas=2, disaggregated=True)


def test_disagg_roundrobin_roles_and_pool_scaling():
    """Even rids prefill, odd rids decode; add_replica(role=...) grows
    the named pool and bare add_replica balances the smaller one."""
    router, _reqs, _refs = _fleet()
    rep = router.add_replica(role="decode")
    assert rep.role == "decode"
    rep2 = router.add_replica()   # prefill pool is now the smaller
    assert rep2.role == "prefill"
    # a combined fleet refuses role'd growth
    plain = Router(_factory, replicas=1)
    with pytest.raises(MXNetError):
        plain.add_replica(role="prefill")
    # never drain the last replica of a role
    small = Router(_factory, replicas=2, disaggregated=True)
    with pytest.raises(MXNetError):
        small.drain_replica(1)


def test_disagg_env_knob_default_inert(monkeypatch):
    """MXTPU_SERVE_DISAGG unset: the router is exactly the combined
    fleet (no roles, per-replica pools); set: disaggregated without
    code changes."""
    monkeypatch.delenv("MXTPU_SERVE_DISAGG", raising=False)
    plain = Router(_factory, replicas=2)
    assert plain.disaggregated is False
    assert all(r.role == "combined" for r in plain.replicas)
    assert len({id(r.engine.cache) for r in plain.replicas}) == 2
    monkeypatch.setenv("MXTPU_SERVE_DISAGG", "1")
    dis = Router(_factory, replicas=2)
    assert dis.disaggregated is True
    assert [r.role for r in dis.replicas] == ["prefill", "decode"]


def test_autoscaler_scales_pools_independently():
    """serving:prefill rules grow the prefill pool on TTFT pressure,
    serving:decode rules the decode pool on TPOT pressure — each with
    its own cooldown; a pool rule against a combined fleet is inert."""
    from mxnet_tpu.elastic import (Autoscaler, ScalingPolicy,
                                   ScalingRule)
    from mxnet_tpu.testing import faults
    clock = faults.FakeClock()
    router = Router(_factory, replicas=2, disaggregated=True)
    scaler = Autoscaler(
        ScalingPolicy([
            ScalingRule("serving.prefill.ttft_ms", high=100.0,
                        domain="serving:prefill", window_s=0.0),
            ScalingRule("serving.decode.tpot_ms", high=50.0,
                        domain="serving:decode", window_s=0.0),
        ], cooldown_s=0.0, max_replicas=3),
        router=router, now=clock)
    d = scaler.tick(signals={"serving.prefill.ttft_ms": 999.0,
                             "serving.decode.tpot_ms": 1.0})
    assert [x["domain"] for x in d] == ["serving:prefill"]
    assert router.replicas[-1].role == "prefill"
    clock.advance(1.0)
    d = scaler.tick(signals={"serving.prefill.ttft_ms": 1.0,
                             "serving.decode.tpot_ms": 999.0})
    assert [x["domain"] for x in d] == ["serving:decode"]
    assert router.replicas[-1].role == "decode"
    # pool-scoped rule against a combined fleet: inert bounds-skip
    plain = Router(_factory, replicas=1)
    s2 = Autoscaler(
        ScalingPolicy([ScalingRule("serving.prefill.ttft_ms",
                                   high=100.0,
                                   domain="serving:prefill",
                                   window_s=0.0)], cooldown_s=0.0),
        router=plain, now=clock)
    assert s2.tick(signals={"serving.prefill.ttft_ms": 999.0}) == []
    assert s2.skipped["bounds"] == 1


@pytest.mark.slow   # composition gate; the chaos serving scenario
# (python -m mxnet_tpu.testing.chaos serving) drives spec-decode fleets per run
def test_disagg_composes_with_spec_decode():
    """MXTPU_SPEC_DECODE on the disaggregated fleet: the decode pool
    drafts+verifies, outputs stay bitwise the PLAIN solo streams."""
    prompts = _prompts(5, seed=4)
    refs = _solo_streams(prompts)

    def spec_factory(compile_cache, kv_cache=None):
        return _factory(compile_cache, kv_cache=kv_cache,
                        spec_decode=True, spec_k=2)

    router = Router(spec_factory, replicas=2, disaggregated=True)
    reqs = [Request(list(p), max_new_tokens=4) for p in prompts]
    for r in reqs:
        router.submit(r)
    router.drive()
    assert [list(r.generated) for r in reqs] == refs
    assert router.stats()["compiles_after_warmup"] == 0
    router._shared_cache.check_leaks(holders=0)


@pytest.mark.slow   # also python -m mxnet_tpu.testing.chaos disagg
def test_chaos_prefill_replica_killed_mid_handoff():
    """The ISSUE 18 acceptance gate: a prefill replica killed BETWEEN
    "prefill finished" and "decode adopted" — zero lost, zero
    duplicated, outputs bitwise solo, shared pool leak-clean."""
    from mxnet_tpu.testing.chaos import run_disagg_scenario
    r = run_disagg_scenario()
    assert r["ok"], r
    assert r["requeues"] >= 1 and r["handoffs"] >= 1


@pytest.mark.slow   # also python -m mxnet_tpu.testing.chaos disagg
def test_chaos_decode_replica_killed_at_boundary():
    """Decode-pool death: adopted requests requeue through a fresh
    prefill, still exactly once and bitwise solo."""
    from mxnet_tpu.testing.chaos import run_disagg_scenario
    r = run_disagg_scenario(kill_rid=1, kill_point="step", kill_at=3)
    assert r["ok"], r
