"""Serving front-end (ISSUE 12): CoW prefix cache, chunked/batched
prefill, multi-replica router.

THE acceptance gates:

- a system prompt shared by >= 3 requests is prefilled exactly ONCE
  (dispatch- and token-counted) and every request's decode logits are
  BITWISE (fp32) the cold-path engine's;
- eviction under block pressure never frees a block a live sequence
  still references (refcount > 0);
- chunked prefill does the same work in strictly fewer dispatches than
  one-prompt-per-boundary, with zero compiles after warmup;
- a replica kill mid-traffic requeues with zero lost/duplicated
  requests and solo-reference outputs (the chaos scenario, also wired
  as ``python -m mxnet_tpu.testing.chaos serving``).

Every engine in this module shares ONE compile cache (the Router's
fleet discipline), so the file pays the graph compiles once.
"""
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu.base import NotSupportedError
from mxnet_tpu.gluon.model_zoo.nlp.llama import (LlamaConfig,
                                                 LlamaForCausalLM)
from mxnet_tpu.serving import (ContinuousBatcher, DoubleFreeError,
                               InferenceEngine, PagedKVCache, PrefixCache,
                               Request, Router)

nd = mx.nd

_CC = {}      # module-wide shared compile cache (one compile per graph)


@pytest.fixture(scope="module")
def net():
    cfg = LlamaConfig(vocab_size=64, hidden_size=32, num_layers=2,
                      num_heads=4, num_kv_heads=2, intermediate_size=64,
                      max_seq_len=64, tie_embeddings=True)
    n = LlamaForCausalLM(cfg)
    n.initialize()
    n(nd.array([[1, 2, 3]], dtype="int32"))
    n.hybridize()
    return n


def _engine(net, prefix=False, chunk=8, **kw):
    kw.setdefault("max_batch", 3)
    kw.setdefault("block_size", 8)
    kw.setdefault("max_context", 32)
    eng = InferenceEngine(net, prefill_chunk=chunk, prefix_cache=prefix,
                          compile_cache=_CC, **kw)
    return eng.warmup()


def _solo_stream(eng, prompt, n_decode):
    """Cold path: full-prompt prefill + greedy decode, capturing the
    decode logits rows."""
    tok, _ = eng.prefill("__solo__", prompt)
    cur = list(prompt) + [int(tok)]
    rows = []
    for _ in range(n_decode):
        pos = len(cur) - 1
        assert eng.reserve("__solo__", pos)
        nxt, lg = eng.decode([("__solo__", cur[-1], pos)])
        rows.append(lg[0].copy())
        cur.append(int(nxt[0]))
    eng.release("__solo__")
    return cur[len(prompt):], rows


# ----------------------------------------------------------------------
# kv-cache refcounts: CoW plumbing + typed errors
# ----------------------------------------------------------------------

def test_refcounts_fork_cow_and_typed_double_free():
    c = PagedKVCache(num_layers=1, num_kv_heads=2, head_dim=8,
                     num_blocks=8, block_size=4, max_batch=2)
    assert c.alloc("a", 8)                       # blocks x2, ref 1 each
    ta = c.table("a")
    c.adopt("b", ta, 8)                          # full share
    assert all(c.refcount(b) == 2 for b in ta)
    # CoW: writing into b's first block must fork it
    copies = c.prepare_write("b", 0, 4)
    assert len(copies) == 1
    old, new = copies[0]
    assert old == ta[0] and new not in ta
    assert c.refcount(old) == 1 and c.refcount(new) == 1
    assert c.cow_copies == 1
    # unshared range: no copies (a's first block is solely a's now)
    assert c.prepare_write("a", 0, 4) == []
    # free only decrements: a's blocks survive b's remaining share
    c.free("a")
    assert c.refcount(ta[1]) == 1               # b still holds it
    assert ta[1] not in c._free
    c.free("b")
    assert c.blocks_in_use == 0
    assert c.check_leaks()
    # typed double free / underflow
    with pytest.raises(DoubleFreeError):
        c.free("a")
    assert c.alloc("d", 4)
    blk = c.table("d")[0]
    c.unref(blk)
    with pytest.raises(DoubleFreeError):
        c.unref(blk)
    with pytest.raises(DoubleFreeError):
        c.ref(blk)                              # unallocated again
    del c._tables["d"], c._lens["d"]            # drop the dangling table


def test_prepare_write_pool_exhausted_rolls_back():
    c = PagedKVCache(num_layers=1, num_kv_heads=2, head_dim=8,
                     num_blocks=4, block_size=4, max_batch=2)
    assert c.alloc("a", 12)                      # all 3 blocks
    c.adopt("b", c.table("a"), 12)
    assert c.prepare_write("b", 0, 4) is None    # no free block to fork
    assert c.alloc_failures == 1
    assert c.cow_copies == 0
    assert c.table("b") == c.table("a")          # plan fully undone
    c.free("a")
    c.free("b")
    assert c.check_leaks()


def test_prefix_cache_chain_lookup_partial_and_lru_eviction():
    c = PagedKVCache(num_layers=1, num_kv_heads=2, head_dim=8,
                     num_blocks=8, block_size=4, max_batch=2)
    pc = PrefixCache(c)
    toks = list(range(10))                       # 2 full blocks + 2 tail
    assert c.alloc("seed", 10)
    pc.insert("seed", toks)                      # nodes: 4,4-full + 2-tail
    assert pc.held_blocks() == 3
    c.free("seed")                               # chains keep the blocks
    assert c.blocks_in_use == 3
    # full-chain hit capped at len-1: an identical prompt reuses the two
    # full blocks and the partial tail
    n, blocks = pc.lookup(toks + [99])
    assert n == 10 and len(blocks) == 3
    # diverging second block: only the first matches
    n, _ = pc.lookup([0, 1, 2, 3, 9, 9, 9, 9, 5])
    assert n == 4
    # miss
    n, _ = pc.lookup([7, 7, 7, 7, 7])
    assert n == 0
    # attach bumps refcounts; eviction must NOT free the shared blocks
    assert pc.attach("req", toks + [42]) == 10
    shared = c.table("req")
    free_before = c.num_free_blocks
    pc.evict(blocks_needed=c.num_blocks)         # drop every chain
    assert pc.held_blocks() == 0
    # chains dropped their refs, but req still holds all three blocks:
    # none may have been recycled
    assert all(c.refcount(b) == 1 for b in shared)
    assert c.num_free_blocks == free_before      # nothing reclaimed
    c.free("req")
    assert c.check_leaks()


# ----------------------------------------------------------------------
# THE gate: shared system prompt prefilled once, decode BITWISE cold
# ----------------------------------------------------------------------

def test_shared_prefix_prefilled_once_and_decode_bitwise(net):
    rng = np.random.RandomState(3)
    sys_prompt = rng.randint(0, 64, (12,)).tolist()
    users = [rng.randint(0, 64, (n,)).tolist() for n in (5, 7, 3)]
    cold = _engine(net, prefix=False)
    refs = [_solo_stream(cold, sys_prompt + u, 4) for u in users]

    eng = _engine(net, prefix=True, num_blocks=25)
    assert eng.pin_prefix(sys_prompt)
    pinned = eng.stats["prompt_tokens_computed"]
    assert pinned == len(sys_prompt)             # computed exactly once
    # serve the three requests; capture each decode's logits rows
    for u, (ref_toks, ref_rows) in zip(users, refs):
        b = ContinuousBatcher(eng)
        rows = []
        orig = eng.decode

        def capture(entries, _orig=orig, _rows=rows):
            nxt, lg = _orig(entries)
            _rows.append(lg[0].copy())
            return nxt, lg

        eng.decode = capture
        req = b.submit(Request(sys_prompt + u, max_new_tokens=5))
        b.run()
        eng.decode = orig
        assert req.generated[:4] == ref_toks[:4]
        for got, ref in zip(rows, ref_rows):
            np.testing.assert_array_equal(
                got, ref, err_msg="prefix-path decode is not bitwise "
                                  "the cold path")
    # the system prompt was never recomputed: only the user suffixes
    assert eng.stats["prompt_tokens_computed"] == \
        pinned + sum(len(u) for u in users)
    assert eng.prefix_cache.hits == 3
    assert eng.prefix_cache.hit_rate() == 1.0
    # decode past the partial tail block forked it per request
    assert eng.cache.cow_copies >= 3
    assert eng.stats["compiles_after_warmup"] == 0
    # leak sweep: all sequences released, only chains hold blocks
    assert eng.cache.check_leaks(
        holders=eng.prefix_cache.held_blocks())


def test_eviction_under_pressure_completes_and_leaks_clean(net):
    """Pool pressure forces LRU chain eviction mid-traffic; live
    requests keep their (refcount > 1) blocks and finish with the cold
    streams; the pool balances afterwards."""
    rng = np.random.RandomState(9)
    sys_prompt = rng.randint(0, 64, (12,)).tolist()
    cold = _engine(net, prefix=False)
    eng = _engine(net, prefix=True, num_blocks=13)   # 12 allocatable
    assert eng.pin_prefix(sys_prompt)
    # unrelated chains to be LRU victims
    for seed in (21, 22):
        eng.pin_prefix(rng.randint(0, 64, (8,)).tolist())
    b = ContinuousBatcher(eng)
    reqs, refs = [], []
    for n in (6, 9, 4, 7):
        prompt = sys_prompt + rng.randint(0, 64, (n,)).tolist()
        refs.append(_solo_stream(cold, prompt, 3)[0])
        reqs.append(b.submit(Request(prompt, max_new_tokens=4)))
    b.run()
    assert all(r.done for r in reqs)
    for r, ref in zip(reqs, refs):
        assert r.generated == ref               # solo-exact streams
    assert eng.prefix_cache.evictions > 0        # pressure actually hit
    assert eng.stats["compiles_after_warmup"] == 0
    assert eng.cache.check_leaks(
        holders=eng.prefix_cache.held_blocks())


# ----------------------------------------------------------------------
# chunked prefill: fewer dispatches for identical work
# ----------------------------------------------------------------------

def test_chunked_prefill_fewer_dispatches_same_work(net):
    rng = np.random.RandomState(5)
    prompts = [rng.randint(0, 64, (3 + i % 5,)).tolist()
               for i in range(6)]

    def serve(eng):
        b = ContinuousBatcher(eng)
        reqs = [b.submit(Request(p, max_new_tokens=3)) for p in prompts]
        b.run()
        return [tuple(r.generated) for r in reqs], b

    serial = _engine(net, prefix=False, chunk=0)
    out_serial, _ = serve(serial)
    chunked = _engine(net, prefix=False, chunk=8)
    out_chunked, bc = serve(chunked)
    assert out_serial == out_chunked                 # identical work
    serial_dispatches = serial.stats["prefill_calls"]
    chunk_dispatches = (chunked.stats["chunk_prefill_calls"]
                        + chunked.stats["prefill_calls"])
    assert serial_dispatches == len(prompts)         # one per boundary
    assert chunk_dispatches < serial_dispatches      # the amortization
    assert serial.stats["compiles_after_warmup"] == 0
    assert chunked.stats["compiles_after_warmup"] == 0
    assert chunked.cache.check_leaks()
    # a long prompt still admits through bounded tail chunks
    long = _engine(net, prefix=False, chunk=8)
    b = ContinuousBatcher(long)
    req = b.submit(Request(rng.randint(0, 64, (20,)).tolist(),
                           max_new_tokens=2))
    b.run()
    assert req.done and len(req.generated) == 2
    assert long.stats["chunk_prefill_calls"] == 3    # ceil(20 / 8)
    assert long.stats["compiles_after_warmup"] == 0


# ----------------------------------------------------------------------
# router: shared warmup, least-loaded admission, death -> requeue
# ----------------------------------------------------------------------

def _router(net, replicas=2, **ekw):
    def factory(_cc):
        # the module-wide cache stands in for the router's: the fleet
        # still pays each graph once (replica engines compile nothing)
        return InferenceEngine(net, max_batch=3, block_size=8,
                               max_context=32, prefill_chunk=8,
                               prefix_cache=True, compile_cache=_CC,
                               **ekw)
    return Router(factory, replicas=replicas)


def test_router_shared_warmup_and_least_loaded_admission(net):
    router = _router(net, replicas=2)
    # the whole fleet compiled nothing new (module cache already warm),
    # and replica 1's warmup skipped every graph replica 0 would build
    for rep in router.replicas:
        assert rep.engine.stats["compiles"] == 0
    m = router.manifest()
    assert m["epoch"] == 0 and len(m["replicas"]) == 2
    assert all(r["mesh"] == "dp1" for r in m["replicas"])
    assert all(r["prefix_cache"] for r in m["replicas"])
    # admission spreads load: queue one replica, the next request must
    # land on the other
    rng = np.random.RandomState(7)
    p = rng.randint(0, 64, (5,)).tolist()
    r1 = router.submit(Request(p, max_new_tokens=2))
    rid1 = router._assigned[r1.id]
    r2 = router.submit(Request(p, max_new_tokens=2))
    assert router._assigned[r2.id] != rid1
    router.drive()
    assert len(router.finished()) == 2
    assert r1.generated == r2.generated              # same prompt


def test_router_death_requeues_zero_lost_or_dup(net):
    from mxnet_tpu.testing import faults
    rng = np.random.RandomState(11)
    sys_prompt = rng.randint(0, 64, (12,)).tolist()
    prompts = [sys_prompt + rng.randint(0, 64, (3 + i,)).tolist()
               for i in range(5)]
    cold = _engine(net, prefix=False)
    refs = [_solo_stream(cold, p, 3)[0] for p in prompts]
    router = _router(net, replicas=2)
    for rep in router.replicas:
        assert rep.engine.pin_prefix(sys_prompt)
    reqs = [router.submit(Request(p, max_new_tokens=4))
            for p in prompts]
    with faults.inject("serving.replica1.step", at=2):
        router.drive()
    fin = router.finished()
    assert sorted(r.id for r in fin) == sorted(r.id for r in reqs)
    assert router.epoch == 1 and router.requeues >= 1
    for r, ref in zip(reqs, refs):
        assert r.generated == ref                   # greedy, solo-exact
    st = router.stats()
    assert st["compiles_after_warmup"] == 0
    assert st["live"] == 1
    # survivor balances: every block back except the prefix chains
    survivor = router.live_replicas()[0]
    assert survivor.engine.cache.check_leaks(
        holders=survivor.engine.prefix_cache.held_blocks())


def test_router_drain_replica_requeues_and_add_replica_grows(net):
    """ISSUE 13: a graceful drain (preemption notice / autoscale-away)
    evacuates the doomed replica with zero lost or duplicated requests,
    and add_replica grows the fleet from the SHARED warmup compile
    cache — the newcomer compiles nothing."""
    rng = np.random.RandomState(17)
    prompts = [rng.randint(0, 64, (4 + i,)).tolist() for i in range(4)]
    router = _router(net, replicas=2)
    reqs = [router.submit(Request(p, max_new_tokens=3))
            for p in prompts]
    moved = router.drain_replica(1, reason="notice:test")
    assert moved >= 1                        # its inbox was evacuated
    assert router.epoch == 1
    assert [e["kind"] for e in router.events] == ["replica_drained"]
    assert not router.replicas[1].alive
    rep = router.add_replica()
    assert rep.rid == 2 and rep.alive and router.epoch == 2
    router.drive()
    fin = router.finished()
    assert sorted(r.id for r in fin) == sorted(r.id for r in reqs)
    assert len(fin) == len(reqs)             # zero lost, zero dup
    assert router.stats()["compiles_after_warmup"] == 0
    # the last live replica refuses to drain (typed, not a wedge)
    router.drain_replica(2, reason="autoscale")
    with pytest.raises(mx.base.MXNetError, match="last live replica"):
        router.drain_replica(0)


def test_router_shedding_rejects_new_admissions_only(net):
    """Degradation-ladder rung 1: shedding rejects NEW submits with the
    typed AdmissionShed; requeues (a drain) are exempt, so in-flight
    work still completes exactly once."""
    from mxnet_tpu.serving import AdmissionShed
    rng = np.random.RandomState(19)
    router = _router(net, replicas=2)
    reqs = [router.submit(Request(rng.randint(0, 64, (5,)).tolist(),
                                  max_new_tokens=2)) for _ in range(2)]
    assert router.set_shedding(True, reason="test") is True
    with pytest.raises(AdmissionShed):
        router.submit(Request([1, 2, 3], max_new_tokens=1))
    router.drain_replica(1, reason="notice:test")   # requeues pass
    router.drive()
    assert all(r.done for r in reqs)
    router.set_shedding(False)
    r3 = router.submit(Request([1, 2, 3], max_new_tokens=1))
    router.drive()
    assert r3.done


def test_router_notice_board_drains_doomed_replica(net):
    """A NoticeBoard wired into the router drains the noticed replica
    at the next drive boundary; a revoked notice cancels the drain."""
    from mxnet_tpu import elastic
    from mxnet_tpu.testing import faults
    clock = faults.FakeClock(100.0)
    board = elastic.NoticeBoard(now=clock)
    router = _router(net, replicas=2)
    router.attach_notices(board)
    rng = np.random.RandomState(23)
    # revoked before any boundary: no drain
    board.post(0, grace_s=60, kind="maintenance")
    board.revoke(0)
    reqs = [router.submit(Request(rng.randint(0, 64, (4,)).tolist(),
                                  max_new_tokens=2)) for _ in range(2)]
    board.post(1, grace_s=60, kind="preempt")
    router.drive()
    assert router.replicas[0].alive          # revocation cancelled it
    assert not router.replicas[1].alive      # the noticed one drained
    assert all(r.done for r in reqs)
    assert board.stats()["pending"] == 0


def test_router_threaded_mode_racecheck_clean(net):
    from mxnet_tpu.lint import racecheck
    racecheck.reset()
    racecheck.configure(enabled=True)
    try:
        router = _router(net, replicas=2)
        router.start()
        rng = np.random.RandomState(13)
        reqs = [router.submit(
            Request(rng.randint(0, 64, (4 + i,)).tolist(),
                    max_new_tokens=2)) for i in range(4)]
        router.wait_all_done(timeout=120)
        router.stop()
        assert all(r.done for r in reqs)
        assert len(router.finished()) == 4
        assert racecheck.findings() == []
    finally:
        racecheck.configure(enabled=False)
        racecheck.reset()


@pytest.mark.slow
def test_serving_chaos_scenario(tmp_path):
    """The tier-1 wiring of the ``serving`` chaos suite (like the elastic
    scenarios): replica kill mid-traffic, requeue, solo-exact outputs,
    flight dump, racecheck, KV leak sweep — one verdict dict."""
    from mxnet_tpu.testing.chaos import run_serving_scenario
    r = run_serving_scenario(workdir=str(tmp_path))
    assert r["ok"], r
    assert r["no_lost_or_dup"] and r["outputs_match_solo"]
    assert r["epoch"] >= 1 and r["requeues"] >= 1
    assert r["kv_leaks_clean"]


# ----------------------------------------------------------------------
# the ISSUE 14 null-honesty fix: an UNMEASURED replica must not win
# admission on a fake-perfect TTFT (r04/r05 null-when-unmeasured)
# ----------------------------------------------------------------------

def test_admission_unmeasured_ttft_is_no_signal_not_perfect(net):
    """Regression: replica 1 has the deeper queue but NO measured
    ttft/kv gauges.  The old ``value(...) or 0.0`` scored it as if it
    had perfect TTFT (6.0 < 7.5) and admitted onto the deeper queue;
    with None treated as "no signal" the scoring falls back to queue
    depth only and the shallower, fully-measured replica 0 wins."""
    from mxnet_tpu import telemetry
    if not telemetry.enabled():
        pytest.skip("telemetry off")
    telemetry.reset()
    router = _router(net, replicas=2)
    telemetry.set_gauge("serving.replica0.queue_depth", 2)
    telemetry.set_gauge("serving.replica0.ttft_ms", 3000.0)
    telemetry.set_gauge("serving.replica0.kv_block_utilization", 0.5)
    telemetry.set_gauge("serving.replica1.queue_depth", 3)
    # replica 1: ttft/kv gauges never published (no traffic measured)
    assert telemetry.value("serving.replica1.ttft_ms") is None
    req = router.submit(Request([1, 2, 3], max_new_tokens=1))
    assert router._assigned[req.id] == 0
    # the signals layer itself reports None, not 0.0
    sig = router._signals(router.replicas[1])
    assert sig["ttft_ms"] is None
    assert sig["kv_block_utilization"] is None
    telemetry.reset()


def test_replica_ttft_gauge_absent_until_measured(net):
    """Direct-read fallback + gauge publication keep the convention:
    before any finished request, load_signals reports ttft_ms=None and
    _step_replica publishes NO ttft gauge (value() stays None); the
    gauge appears only once a real TTFT was measured."""
    from mxnet_tpu import telemetry
    if not telemetry.enabled():
        pytest.skip("telemetry off")
    telemetry.reset()
    router = _router(net, replicas=2)
    rep = router.replicas[0]
    assert rep.load_signals()["ttft_ms"] is None
    router._step_replica(rep)              # idle boundary publishes...
    assert telemetry.value("serving.replica0.queue_depth") == 0
    assert telemetry.value("serving.replica0.ttft_ms") is None  # ...no ttft
    rng = np.random.RandomState(23)
    req = router.submit(Request(rng.randint(0, 64, (4,)).tolist(),
                                max_new_tokens=2))
    router.drive()
    rid = router._assigned[req.id]
    assert telemetry.value(f"serving.replica{rid}.ttft_ms") is not None
    telemetry.reset()


# ----------------------------------------------------------------------
# the ISSUE 12 small fix: typed TP rejection + recorded MeshConfig
# ----------------------------------------------------------------------

def test_engine_typed_tp_rejection_and_mesh_recorded(net):
    # a STRUCTURALLY tensor-parallel net (cfg.tensor_parallel) is still
    # typed-rejected: the engine shards plain weights itself (ISSUE 18)
    cfg = LlamaConfig(vocab_size=32, hidden_size=16, num_layers=1,
                      num_heads=2, num_kv_heads=2, intermediate_size=32,
                      tensor_parallel=True)
    with pytest.raises(NotSupportedError) as ei:
        InferenceEngine(LlamaForCausalLM(cfg))
    assert "MeshConfig" in str(ei.value)   # names the supported path
    # a pp mesh is typed-rejected; dp AND tp meshes are recorded
    with pytest.raises(NotSupportedError):
        InferenceEngine(net, mesh="dp1tp1pp2")
    eng = InferenceEngine(net, max_batch=3, block_size=8,
                          max_context=32, mesh="dp4",
                          compile_cache=_CC)
    assert eng.mesh_config.describe() == "dp4"
    assert eng.mesh_config.dp == 4
    # ISSUE 18: a tp submesh is ACCEPTED — weights sharded at rest, the
    # mesh spec in the compile-cache signature (no warmup here: init
    # must stay compile-free)
    eng2 = InferenceEngine(net, max_batch=2, block_size=8,
                           max_context=32, mesh="dp1tp2",
                           compile_cache={})
    assert eng2.mesh_config.tp == 2 and eng2.tp == 2
    assert eng2.mesh_config.describe() in \
        eng2._sig("decode", 1)


def test_lifecycle_gauges_present(net):
    """The new telemetry gauges ride the engine lifecycle."""
    from mxnet_tpu import telemetry
    if not telemetry.enabled():
        pytest.skip("telemetry off")
    telemetry.reset()
    eng = _engine(net, prefix=True)
    rng = np.random.RandomState(17)
    sp = rng.randint(0, 64, (9,)).tolist()
    assert eng.pin_prefix(sp)
    b = ContinuousBatcher(eng)
    b.submit(Request(sp + [1, 2], max_new_tokens=2))
    b.run()
    assert telemetry.value("serving.kv_blocks_in_use") is not None
    assert telemetry.value("serving.prefix_hit_rate") == 1.0
    assert telemetry.value("serving.chunk_prefill_calls") >= 1
