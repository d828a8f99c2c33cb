"""Serving engine (ISSUE 7): paged KV cache, AOT bucketed prefill/decode,
continuous batching, int8 serving, decode-parity gates.

THE parity contract (the llama.py:56 "one source so decode parity can't
drift" promise, finally enforced): decode-with-KV-cache logits are
BITWISE equal (fp32) to the hybridized full forward evaluated at the
decode's context-bucket width (prompt padded to the bucket, logits read
at the last valid row).  The bucket-width reference is the precise
statement of what fixed-shape serving computes: XLA's reduce order
changes with the summation WIDTH (empirically: zero-padded reductions
are width-stable up to 16 elements and at equal widths, not across
different >16 widths), so the engine matches the full forward exactly
when both run at the same padded width — which is also how a batch
verifier would run the forward in production.  Against the UNPADDED
forward the logits agree to float eps and the argmax/token stream is
identical (gated below too).
"""
import json

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu.gluon.model_zoo.nlp.llama import (LlamaConfig,
                                                 LlamaForCausalLM)
from mxnet_tpu.serving import (ContinuousBatcher, InferenceEngine,
                               PagedKVCache, Request, StaticBatcher,
                               next_bucket, serving_block)

nd = mx.nd


def _net(tie=True, vocab=64, layers=2):
    cfg = LlamaConfig(vocab_size=vocab, hidden_size=32, num_layers=layers,
                      num_heads=4, num_kv_heads=2, intermediate_size=64,
                      max_seq_len=64, tie_embeddings=tie)
    net = LlamaForCausalLM(cfg)
    net.initialize()
    net(nd.array([[1, 2, 3]], dtype="int32"))     # materialize shapes
    net.hybridize()   # the engine mirrors ONE fused graph; the eager
    # op-by-op forward differs by fusion (FMA) — hybridized is both the
    # production path and the parity reference
    return net


def _ref_last_logits(net, tokens, width):
    """Full-forward logits at the last valid position, evaluated at the
    padded ``width`` (the decode bucket)."""
    pad = np.zeros((1, width), np.int32)
    pad[0, :len(tokens)] = tokens
    return net(nd.array(pad, dtype="int32")).asnumpy()[0, len(tokens) - 1]


def _drive(eng, slot, prompt, n_steps, check=None):
    """Prefill + n_steps greedy decode; calls check(cur, pos, logits)
    after every decode step.  Returns the generated ids."""
    tok, _ = eng.prefill(slot, prompt)
    cur = list(prompt) + [int(tok)]
    for _ in range(n_steps):
        pos = len(cur) - 1
        assert eng.reserve(slot, pos)
        nxt, lg = eng.decode([(slot, cur[-1], pos)])
        if check is not None:
            check(cur, pos, lg[0])
        cur.append(int(nxt[0]))
    return cur[len(prompt):]


# ----------------------------------------------------------------------
# paged KV cache
# ----------------------------------------------------------------------

def test_paged_cache_alloc_free_reuse():
    c = PagedKVCache(num_layers=1, num_kv_heads=2, head_dim=8,
                     num_blocks=9, block_size=4, max_batch=2)
    assert c.num_free_blocks == 8          # block 0 reserved
    assert c.alloc("a", 10)                # 3 blocks
    assert c.blocks_in_use == 3
    assert c.alloc("b", 17)                # 5 blocks
    assert c.num_free_blocks == 0
    assert not c.alloc("c", 1)             # exhausted
    assert c.alloc_failures == 1
    # grow a: needs a 4th block -> fails until b frees
    assert not c.ensure("a", 12)
    c.free("b")
    assert c.ensure("a", 12)
    assert c.blocks_in_use == 4
    # trim back to 10 tokens -> 3 blocks again, freed block reusable
    c.trim("a", 10)
    assert c.blocks_in_use == 3
    # table_array pads with the null block and respects width
    arr = c.table_array(["a", None], 4)
    assert arr.shape == (2, 4)
    assert (arr[1] == 0).all()
    assert (arr[0, :3] > 0).all() and arr[0, 3] == 0
    c.free("a")
    assert c.blocks_in_use == 0 and c.utilization() == 0.0
    # block 0 is never handed out
    assert c.alloc("d", 32)
    assert 0 not in c.table("d")


def test_cache_rejects_bad_config():
    with pytest.raises(mx.MXNetError):
        PagedKVCache(1, 2, 8, num_blocks=4, block_size=3)   # not pow2
    with pytest.raises(mx.MXNetError):
        PagedKVCache(1, 2, 8, num_blocks=1)                 # no null blk
    c = PagedKVCache(1, 2, 8, num_blocks=4, block_size=4)
    assert c.alloc("a", 4)
    with pytest.raises(mx.MXNetError):
        c.alloc("a", 4)                                     # double alloc


# ----------------------------------------------------------------------
# decode parity: THE gate
# ----------------------------------------------------------------------

@pytest.mark.parametrize("tie", [True, False])
def test_decode_parity_bitwise_per_bucket(tie):
    """Across every shape bucket (8/16/32, including the 8->16->32
    crossings), decode-with-cache logits == hybridized full forward at
    the bucket width, BITWISE in fp32, for every generated position."""
    net = _net(tie=tie)
    eng = InferenceEngine(net, max_batch=2, block_size=8, max_context=32)
    eng.warmup()
    rng = np.random.RandomState(3)
    checked = [0]

    def make_check():
        def check(cur, pos, logits):
            bucket = next_bucket(pos + 1, eng.buckets)
            ref = _ref_last_logits(net, cur, bucket)
            np.testing.assert_array_equal(
                logits, ref,
                err_msg=f"decode at pos {pos} (bucket {bucket}) is not "
                        "bitwise the full forward")
            checked[0] += 1
        return check

    # one prompt per bucket entry point; each decodes to max_context-1,
    # so the 5-token prompt crosses 8 -> 16 -> 32 inside one sequence
    for slot, t0 in enumerate((5, 9, 17)):
        prompt = rng.randint(0, 64, (t0,)).tolist()
        _drive(eng, slot, prompt, 31 - t0, check=make_check())
        eng.release(slot)
    assert checked[0] >= 60
    assert eng.stats["compiles_after_warmup"] == 0


def test_engine_from_default_initialized_net_holds_no_gradient_arrays():
    """A net built and initialized with the defaults (``grad_req`` is
    'write' everywhere) serves without a second array a parameter: the
    tape allocates a gradient only when a backward or a ``.grad`` read
    asks for one (ISSUE 39; it used to double the served weights)."""
    net = _net()
    params = list(net.collect_params().values())
    assert all(p.grad_req == "write" for p in params)
    eng = InferenceEngine(net, max_batch=2, block_size=8, max_context=32)
    out = _drive(eng, 0, [1, 2, 3, 4, 5], 4)
    assert len(out) == 5
    assert all(p._data._grad is None for p in params)


def test_prefill_parity_bitwise_per_bucket():
    """Prefill (padded and bucket-exact prompts) reproduces the full
    forward's last-position logits bitwise, and samples its argmax."""
    net = _net(tie=False)
    eng = InferenceEngine(net, max_batch=2, block_size=8, max_context=32)
    eng.warmup()
    rng = np.random.RandomState(5)
    for slot, t0 in enumerate((3, 8, 12, 16, 25, 32)):
        prompt = rng.randint(0, 64, (t0,)).tolist()
        tok, logits = eng.prefill(slot, prompt)
        bucket = next_bucket(t0, eng.buckets)
        ref = _ref_last_logits(net, prompt, bucket)
        np.testing.assert_array_equal(logits, ref)
        assert tok == int(ref.argmax())
        eng.release(slot)


# the float-eps-vs-unpadded + net.generate() stream twin of the BITWISE
# per-bucket decode/prefill parity gates above
def test_decode_close_to_unpadded_forward_and_matches_generate():
    """User-visible guarantees vs the UNPADDED forward: logits to float
    eps and the greedy token stream identical to net.generate()."""
    net = _net(tie=True)
    eng = InferenceEngine(net, max_batch=2, block_size=8, max_context=32)
    eng.warmup()
    prompt = np.random.RandomState(0).randint(0, 64, (5,)).tolist()

    def check(cur, pos, logits):
        # every unpadded width is a fresh reference compile — 8 steps
        # cover the 8->16 bucket crossing without burning tier-1 budget
        ref = net(nd.array([cur], dtype="int32")).asnumpy()[0, -1]
        np.testing.assert_allclose(logits, ref, atol=1e-5, rtol=1e-5)
        assert int(logits.argmax()) == int(ref.argmax())

    got = _drive(eng, 0, prompt, 8, check=check)
    ref = net.generate(nd.array([prompt], dtype="int32"), 9,
                       temperature=0.0).asnumpy()[0, 5:]
    np.testing.assert_array_equal(np.asarray(got), ref)


def test_joined_batch_rows_match_single_sequence():
    """Sequences decoding JOINED in one batch produce the same logits
    rows as each would alone (batch-dim stability — continuous batching
    can't perturb a neighbour's numerics)."""
    net = _net(tie=True)
    rng = np.random.RandomState(7)
    pa = rng.randint(0, 64, (5,)).tolist()
    pb = rng.randint(0, 64, (11,)).tolist()
    # solo runs
    eng1 = InferenceEngine(net, max_batch=2, block_size=8, max_context=32)
    eng1.warmup()
    solo = {}
    for slot, p in ((0, pa), (1, pb)):
        logits_rows = []
        _drive(eng1, slot, p, 4,
               check=lambda cur, pos, lg, rows=logits_rows:
               rows.append(lg.copy()))
        solo[slot] = logits_rows
    # joined run on a fresh engine: prefill both, decode as one batch
    eng2 = InferenceEngine(net, max_batch=2, block_size=8, max_context=32)
    eng2.warmup()
    ta, _ = eng2.prefill(0, pa)
    tb, _ = eng2.prefill(1, pb)
    cura, curb = list(pa) + [int(ta)], list(pb) + [int(tb)]
    for step in range(4):
        poa, pob = len(cura) - 1, len(curb) - 1
        assert eng2.reserve(0, poa) and eng2.reserve(1, pob)
        nxt, lg = eng2.decode([(0, cura[-1], poa), (1, curb[-1], pob)])
        # NOTE the joined step runs at the max of the two context
        # buckets; row parity vs solo holds when both land in the same
        # bucket zone (<=16-stable or same bucket) — positions here stay
        # within bucket 16 for both, so rows must be bitwise
        np.testing.assert_array_equal(lg[0], solo[0][step])
        np.testing.assert_array_equal(lg[1], solo[1][step])
        cura.append(int(nxt[0]))
        curb.append(int(nxt[1]))


# ----------------------------------------------------------------------
# int8 serving (quantize_net wiring)
# ----------------------------------------------------------------------

# slow-marked (ISSUE 18 tier-1 headroom): quantize_net numerics stay
# covered by test_quantization; the engine wiring by the int8 loadgen
@pytest.mark.slow
@pytest.mark.slow   # int8 WEIGHT serving end-to-end; the int8 math is
# gated fast in test_quantization and low-precision serving in
# test_quant_kv (ISSUE 20 tier-1 headroom)
def test_int8_engine_bitwise_vs_quantized_net_and_bounded_vs_fp32():
    """int8 serving: the engine's decode mirrors QuantizedDense
    op-for-op, so parity vs the QUANTIZED net's own (bucket-width)
    forward stays BITWISE — int32 accumulation is exact — while drift
    vs the fp32 snapshot stays inside the documented bound
    (docs/SERVING.md: |logit drift| <= 0.05 * max|logit|)."""
    net = _net(tie=False)
    rng = np.random.RandomState(1)
    prompt = rng.randint(0, 64, (5,)).tolist()
    calib = [nd.array(rng.randint(0, 64, (2, 12)), dtype="int32")
             for _ in range(2)]
    fp32_ref = _ref_last_logits(net, prompt, 8)
    eng = InferenceEngine(net, max_batch=2, block_size=8, max_context=32,
                          quantize="int8", calib_data=calib)
    assert eng.quantized
    eng.warmup()
    tok, logits = eng.prefill(0, prompt)
    qref = _ref_last_logits(net, prompt, 8)      # net is now int8
    np.testing.assert_array_equal(logits, qref)
    drift = np.abs(np.asarray(logits) - fp32_ref).max()
    assert drift <= 0.05 * np.abs(fp32_ref).max()

    def check(cur, pos, lg):
        bucket = next_bucket(pos + 1, eng.buckets)
        np.testing.assert_array_equal(
            lg, _ref_last_logits(net, cur, bucket))

    _drive_from = list(prompt) + [int(tok)]
    cur = _drive_from
    for _ in range(8):
        pos = len(cur) - 1
        assert eng.reserve(0, pos)
        nxt, lg = eng.decode([(0, cur[-1], pos)])
        check(cur, pos, lg[0])
        cur.append(int(nxt[0]))
    assert eng.stats["compiles_after_warmup"] == 0


def test_engine_rejects_tp_and_bad_quantize():
    cfg = LlamaConfig(vocab_size=32, hidden_size=16, num_layers=1,
                      num_heads=2, num_kv_heads=2, intermediate_size=32,
                      tensor_parallel=True)
    with pytest.raises(mx.MXNetError):
        InferenceEngine(LlamaForCausalLM(cfg))
    net = _net()
    with pytest.raises(mx.MXNetError):
        InferenceEngine(net, quantize="int4")
    with pytest.raises(mx.MXNetError):
        InferenceEngine(net, quantize="int8")    # no calib_data


# ----------------------------------------------------------------------
# scheduler: full lifecycle, continuous vs static
# ----------------------------------------------------------------------

def test_full_request_lifecycle_slot_reuse_zero_retraces():
    """enqueue -> prefill -> joined decode -> EOS/length -> slot reuse,
    with ZERO compiles after warmup (the compile-cache counter is the
    retrace gate) and every block back in the pool at the end."""
    net = _net(tie=True, vocab=64)
    eng = InferenceEngine(net, max_batch=2, block_size=8, max_context=32)
    eng.warmup()
    # discover the token greedy decode settles on, to exercise the EOS
    # path deterministically
    rng = np.random.RandomState(2)
    probe = net.generate(nd.array([rng.randint(0, 64, (4,)).tolist()],
                                  dtype="int32"), 8,
                         temperature=0.0).asnumpy()[0]
    eos_tok = int(probe[-1])
    batcher = ContinuousBatcher(eng)
    reqs = []
    for i in range(5):   # 5 requests through 2 slots -> slots reused
        prompt = rng.randint(0, 64, (3 + 2 * i,)).tolist()
        eos = eos_tok if i == 0 else None
        reqs.append(batcher.submit(Request(prompt, max_new_tokens=6,
                                           eos_id=eos)))
    stats = batcher.run()
    assert stats["requests"] == 5
    assert all(r.done for r in reqs)
    assert reqs[0].finish_reason in ("eos", "length")
    assert any(r.finish_reason == "length" for r in reqs)
    for r in reqs:
        assert 1 <= len(r.generated) <= 6
        assert r.latency() is not None and r.ttft() is not None
    # slots fully recycled, pool drained, nothing recompiled
    assert len(batcher._free_slots) == eng.max_batch
    assert eng.cache.stats()["sequences"] == 0
    assert eng.cache.blocks_in_use == 0
    assert eng.stats["compiles_after_warmup"] == 0
    assert stats["occupancy"] > 0
    # ISSUE 12 hygiene: the refcount sweep balances (no dangling holds),
    # the in-use gauge went back to zero, and a second release of an
    # already-freed slot is the typed double-free
    assert eng.cache.check_leaks()
    from mxnet_tpu import telemetry
    from mxnet_tpu.serving import DoubleFreeError
    if telemetry.enabled():
        assert telemetry.value("serving.kv_blocks_in_use") == 0
    with pytest.raises(DoubleFreeError):
        eng.release(0)


@pytest.mark.slow
def test_continuous_beats_static_on_mixed_lengths():
    """The acceptance gate, on deterministic quantities: same request
    mix, same engine graphs — continuous batching needs FEWER decode
    steps (higher tokens/step) and holds HIGHER occupancy than static,
    because finished slots refill at token boundaries instead of idling
    until the batch drains."""
    from tools.serve_loadgen import run_loadgen
    payload = run_loadgen(n_requests=8, max_batch=3, block_size=8,
                          max_context=64, mode="both", smoke=True)
    c = payload["policies"]["continuous"]
    s = payload["policies"]["static"]
    assert c["tokens_generated"] == s["tokens_generated"]   # same work
    assert c["decode_steps"] < s["decode_steps"]
    assert c["occupancy"] > s["occupancy"]
    assert c["tokens_per_step"] > s["tokens_per_step"]
    assert c["compiles_after_warmup"] == 0
    assert s["compiles_after_warmup"] == 0
    # the serving block is the bench schema and it round-trips
    blk = payload["serving"]
    assert set(blk) >= set(serving_block())
    assert json.loads(json.dumps(payload)) == payload


def test_pool_exhaustion_keeps_requests_queued():
    """A request that can't get blocks stays queued (alloc is atomic —
    no partial allocation) and is admitted once a slot frees."""
    net = _net(tie=True)
    # pool sized so only ~one long sequence fits at a time
    eng = InferenceEngine(net, max_batch=2, block_size=8, max_context=32,
                          num_blocks=6)
    eng.warmup()
    rng = np.random.RandomState(4)
    batcher = ContinuousBatcher(eng)
    for _ in range(3):
        batcher.submit(Request(rng.randint(0, 64, (17,)).tolist(),
                               max_new_tokens=3))
    stats = batcher.run()
    assert stats["requests"] == 3
    assert eng.cache.blocks_in_use == 0
    assert eng.cache.alloc_failures > 0       # exhaustion actually hit


def test_request_finishing_inside_prefill_is_progress():
    """max_new_tokens=1 (or EOS on the prefill-sampled token) completes
    the request inside the prefill boundary; the scheduler must count
    that as progress, not a stuck queue (regression: run() raised
    'cannot be admitted' when an admitted request never reached the
    decode batch)."""
    net = _net(tie=True)
    eng = InferenceEngine(net, max_batch=2, block_size=8, max_context=16)
    eng.warmup()
    b = ContinuousBatcher(eng)
    one = b.submit(Request([5], max_new_tokens=1))
    two = b.submit(Request([1, 2], max_new_tokens=2))
    stats = b.run()
    assert stats["requests"] == 2
    assert one.finish_reason == "length" and len(one.generated) == 1
    assert len(two.generated) == 2
    # EOS hit by the very token prefill samples
    tok, _ = eng.prefill(9, [7, 8])
    eng.release(9)
    b2 = ContinuousBatcher(eng)
    r = b2.submit(Request([7, 8], max_new_tokens=5, eos_id=int(tok)))
    b2.run()
    assert r.finish_reason == "eos" and len(r.generated) == 1
    # static baseline: a whole batch finishing in prefill is legal
    s = StaticBatcher(eng)
    for _ in range(3):
        s.submit(Request([5], max_new_tokens=1))
    st = s.run()
    assert st["requests"] == 3 and st["decode_steps"] == 0
    assert eng.cache.blocks_in_use == 0


def test_prompt_longer_than_max_context_rejected():
    net = _net(tie=True)
    eng = InferenceEngine(net, max_batch=2, block_size=8, max_context=16)
    eng.warmup()
    batcher = ContinuousBatcher(eng)
    batcher.submit(Request(list(range(1, 30)), max_new_tokens=2))
    with pytest.raises(mx.MXNetError):
        batcher.run()


# ----------------------------------------------------------------------
# loadgen smoke (the tier-1 wiring of tools/serve_loadgen.py)
# ----------------------------------------------------------------------

def test_serve_loadgen_smoke_cli():
    """`tools/serve_loadgen.py --smoke` runs end-to-end and prints one
    JSON line under the driver's tail-window budget."""
    import tools.serve_loadgen as slg
    payload = slg.run_loadgen(n_requests=6, max_batch=2, block_size=8,
                              max_context=32, mode="both", smoke=True)
    line = json.dumps({k: v for k, v in payload.items()
                       if k != "policies"})
    assert len(line) < 1800
    blk = payload["serving"]
    assert blk["compiles_after_warmup"] == 0
    assert blk["tokens_s"] is not None and blk["occupancy"] is not None
    assert payload["continuous_vs_static"]["tokens_per_step_ratio"] > 1.0


def test_sampler_accepts_compiled_step_function():
    """SequenceSampler/BeamSearchSampler drive a raw jax.jit step
    function (no NDArray wrapping, logits stay on device)."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.gluon.model_zoo.nlp.sampler import (BeamSearchSampler,
                                                       SequenceSampler)
    vocab = 16

    @jax.jit
    def step(tok, states):
        # favour (tok + 1) % vocab; EOS=0 reachable from tok 15
        lp = jax.nn.log_softmax(
            10.0 * jax.nn.one_hot((tok + 1) % vocab, vocab), axis=-1)
        return lp, states
    beam = BeamSearchSampler(beam_size=2, decoder=step, eos_id=0,
                             max_length=20, sync_every=4)
    samples, scores, lengths = beam(mx.nd.array([14, 3]), {})
    s = samples.asnumpy()
    assert s.shape[:2] == (2, 2)
    assert s[0, 0, 1] == 15 and 0 in s[0, 0, 2:]     # 14 -> 15 -> EOS
    smp = SequenceSampler(beam_size=2, decoder=step, eos_id=0,
                          max_length=8, temperature=1.0, top_k=2)
    samples, scores, lengths = smp(mx.nd.array([5]), {})
    assert samples.shape[0] == 1 and samples.shape[1] == 2


# ----------------------------------------------------------------------
# the `serving` block schema (ISSUE 7): config always real, measured
# fields null-when-unmeasured — a CPU run can't fake serving latency
# ----------------------------------------------------------------------

_SERVING_KEYS = {
    "max_batch", "block_size", "buckets", "quantized", "continuous",
    "requests", "p50_ms", "p99_ms", "ttft_p50_ms", "tokens_s",
    "tokens_s_chip", "occupancy", "tokens_per_step",
    "compiles_after_warmup", "cache_utilization",
    # ISSUE 12 front-end fields
    "chunked_prefill", "router_replicas", "prefix_hit_rate",
    "router_p99_ms",
    # ISSUE 17 speculative-decoding fields
    "speculative", "paged_attn", "spec_accept_rate",
    "tokens_per_dispatch",
    # ISSUE 18 sharded/disaggregated fleet fields
    "tp_shards", "disaggregated", "handoff_ms",
    "prefill_pool_occupancy", "decode_pool_occupancy",
    # ISSUE 20 low-precision KV fields
    "kv_dtype", "kv_capacity_ratio", "kv_decode_drift",
}


def test_serving_block_schema_is_stable():
    from mxnet_tpu.serving import serving_block
    blk = serving_block()
    assert set(blk) == _SERVING_KEYS
    # MEASURED fields are null when nothing was measured
    for k in ("p50_ms", "p99_ms", "ttft_p50_ms", "tokens_s",
              "tokens_s_chip", "occupancy", "tokens_per_step",
              "compiles_after_warmup", "cache_utilization",
              "prefix_hit_rate", "router_p99_ms", "spec_accept_rate",
              "tokens_per_dispatch", "handoff_ms",
              "prefill_pool_occupancy", "decode_pool_occupancy",
              "kv_capacity_ratio", "kv_decode_drift"):
        assert blk[k] is None, k
    # CONFIG fields are always real (front-end off by default)
    assert blk["chunked_prefill"] is False
    assert blk["router_replicas"] == 0
    assert blk["speculative"] is False
    assert blk["paged_attn"] is False
    assert blk["tp_shards"] == 0
    assert blk["disaggregated"] is False
    assert blk["kv_dtype"] == "fp32"
    # measured values round-trip, rounded
    blk2 = serving_block(p99_ms=12.3456, tokens_s_chip=901.239,
                         occupancy=0.87654, compiles_after_warmup=0,
                         chunked_prefill=True, router_replicas=4,
                         prefix_hit_rate=0.98765, router_p99_ms=77.7777,
                         speculative=True, paged_attn=True,
                         spec_accept_rate=0.61239,
                         tokens_per_dispatch=2.71828,
                         tp_shards=2, disaggregated=True,
                         handoff_ms=0.12345,
                         prefill_pool_occupancy=0.43219,
                         decode_pool_occupancy=0.87654)
    assert blk2["p99_ms"] == 12.346
    assert blk2["tokens_s_chip"] == 901.2
    assert blk2["occupancy"] == 0.8765
    assert blk2["compiles_after_warmup"] == 0
    assert blk2["chunked_prefill"] is True
    assert blk2["router_replicas"] == 4
    assert blk2["prefix_hit_rate"] == 0.9877
    assert blk2["router_p99_ms"] == 77.778
    assert blk2["speculative"] is True
    assert blk2["paged_attn"] is True
    assert blk2["spec_accept_rate"] == 0.6124
    assert blk2["tokens_per_dispatch"] == 2.718
    assert blk2["tp_shards"] == 2
    assert blk2["disaggregated"] is True
    assert blk2["handoff_ms"] == 0.123
    assert blk2["prefill_pool_occupancy"] == 0.4322
    assert blk2["decode_pool_occupancy"] == 0.8765
    assert json.loads(json.dumps(blk)) == blk


def test_loadgen_compiles_counter_reads_through_telemetry():
    """The loadgen's compiles_after_warmup is a before/after DELTA off
    the process registry (one source of truth), so a second engine in
    the same process cannot inherit the first one's count."""
    from mxnet_tpu import telemetry
    if not telemetry.enabled():
        return
    telemetry.reset()
    # simulate an earlier engine's post-warmup compile in this process
    telemetry.inc("serving.compiles_after_warmup", 3)
    import tools.serve_loadgen as slg
    payload = slg.run_loadgen(n_requests=2, max_batch=2, block_size=8,
                              max_context=64, mode="continuous",
                              smoke=True)
    blk = payload["serving"]
    # the measured WINDOW saw zero compiles even though the process
    # counter started at 3 — and the KV utilization gauge rode along
    assert blk["compiles_after_warmup"] == 0
    assert blk["cache_utilization"] is not None
