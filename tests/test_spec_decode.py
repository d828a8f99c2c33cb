"""Speculative decoding through the compiled-step seam (ISSUE 17).

THE acceptance gates:

- greedy speculative output is BITWISE (fp32 argmax-exact) the
  non-speculative decode stream across K in {2, 3} here plus {4, 8} in
  the slow-marked twin (the W=8/W=16 verify compiles are single-core
  XLA time tier-1 cannot spare), including when per-sequence fallback
  kicks in mid-stream (always-missing drafts);
- identical work takes STRICTLY FEWER engine dispatches speculatively
  (deterministic CPU count, not a walltime claim);
- ``compiles_after_warmup`` stays 0 under speculative traffic (the
  verify family is warmup-compiled like every other graph);
- ``MXTPU_SPEC_DECODE`` unset/0 is a bitwise-inert kill switch (spec
  off = the plain engine: zero verify dispatches, same stream);
- the acceptance-rate gauge is published from real accounting;
- the PrefixCache draft-source trie walk (``continuation``) is
  refcount-NEUTRAL, respects partial tails, and degrades to "no draft"
  (never a crash) when chains are evicted mid-draft.

Every engine here shares ONE compile cache: the verify signatures
carry the width bucket, so the K=3 engine's {2,4} widths cover the
K=2 engine's buckets and the file pays each compile once (in the
``warm`` fixture's setup, outside any test's call budget).
"""
import os

import pytest

import mxnet_tpu as mx
from mxnet_tpu.base import MXNetError, NotSupportedError
from mxnet_tpu.gluon.model_zoo.nlp.llama import (LlamaConfig,
                                                 LlamaForCausalLM)
from mxnet_tpu.serving import (ContinuousBatcher, DraftSource,
                               InferenceEngine, PagedKVCache,
                               PrefixCache, Request)

nd = mx.nd

_VOCAB = 48
_CC = {}      # module-wide shared compile cache (one compile per graph)

# self-repeating prompts: the prompt-lookup n-gram source fires on the
# trailing gram, so speculative boundaries really draft
_PROMPTS = ((1, 2, 3, 1, 2, 3, 1),
            (5, 6, 7, 5, 6),
            (9, 10, 9, 10, 9, 10))
_MAX_NEW = 6


@pytest.fixture(scope="module")
def net():
    # one layer keeps the verify-family compiles inside the tier-1 time
    # budget; multi-layer speculative decode runs in the slow chaos
    # scenarios (2-layer nets, under MXTPU_SPEC_DECODE=1)
    cfg = LlamaConfig(vocab_size=_VOCAB, hidden_size=32, num_layers=1,
                      num_heads=4, num_kv_heads=2, intermediate_size=64,
                      max_seq_len=64, tie_embeddings=True)
    n = LlamaForCausalLM(cfg)
    n.initialize()
    n(nd.array([[1, 2, 3]], dtype="int32"))
    n.hybridize()
    return n


def _engine(net, **kw):
    # single context bucket (block_size == max_context): the
    # bucket-crossing machinery has its own gates in test_serving.py;
    # here one n_blocks keeps the verify family at 4 compiles total
    # (block-boundary speculation runs in the slow chaos scenario)
    kw.setdefault("max_batch", 3)
    kw.setdefault("block_size", 16)
    kw.setdefault("max_context", 16)
    eng = InferenceEngine(net, prefix_cache=False, compile_cache=_CC,
                          **kw)
    return eng.warmup()


@pytest.fixture(scope="module")
def warm(net):
    """Pay every compile ONCE, in fixture setup: the spec_k=3 warmup
    covers the verify widths {2,4} plus the base graphs, so each
    test's call phase stays inside the tier-1 duration budget.  Every
    wider verify graph is single-core XLA time the tier-1 clock cannot
    spare (W=8 ~6 s, W=16 ~12 s) — those compile in the slow-marked
    K∈{8,4} twin."""
    _engine(net, spec_decode=True, spec_k=3)


def _run(net, **kw):
    """The standard mix through a fresh engine + batcher; returns
    (engine, batcher, {prompt: generated})."""
    eng = _engine(net, **kw)
    b = ContinuousBatcher(eng)
    for p in _PROMPTS:
        b.submit(Request(list(p), max_new_tokens=_MAX_NEW))
    b.run()
    outs = {tuple(r.tokens): list(r.generated) for r in b.finished}
    assert len(outs) == len(_PROMPTS)
    return eng, b, outs


@pytest.fixture(scope="module")
def ref_run(net, warm):
    """The plain greedy stream under DEFAULT env — doubling as the
    kill-switch baseline: MXTPU_SPEC_DECODE unset means no verify
    graphs, no drafts, the pre-speculative engine."""
    os.environ.pop("MXTPU_SPEC_DECODE", None)
    eng, b, outs = _run(net)
    assert eng.spec_decode is False and b.speculative is False
    assert b.verify_steps == 0 and eng.stats["verify_calls"] == 0
    assert eng.cache.check_leaks()
    return eng.stats["decode_calls"], outs


# ----------------------------------------------------------------------
# the tentpole gate: bitwise-greedy parity across K, fewer dispatches,
# zero compiles after warmup
# ----------------------------------------------------------------------

def _assert_parity(net, ref_run, ks):
    from mxnet_tpu import telemetry
    plain_dispatches, ref = ref_run
    for k in ks:
        eng, b, outs = _run(net, spec_decode=True, spec_k=k)
        assert outs == ref, f"spec_k={k} diverged from plain greedy"
        assert eng.stats["compiles_after_warmup"] == 0
        assert b.verify_steps > 0 and eng.stats["verify_calls"] > 0
        assert eng.stats["draft_tokens_scored"] > 0
        st = b.stats()
        assert st["spec_accept_rate"] is not None
        assert st["tokens_per_dispatch"] is not None
        # strictly fewer dispatches for identical work: every verify
        # call replaces >= 1 plain decode, accepted drafts replace more
        spec_dispatches = (eng.stats["decode_calls"]
                          + eng.stats["verify_calls"])
        assert spec_dispatches < plain_dispatches, \
            f"spec_k={k}: {spec_dispatches} vs plain {plain_dispatches}"
        assert eng.cache.check_leaks()
        if telemetry.enabled():
            assert telemetry.value("serving.spec_accept_rate") \
                is not None


def test_speculative_bitwise_parity_across_k(net, ref_run):
    # the larger K first: its verify widths {2,4} superset K=2's in
    # the shared cache (one compile, via `warm`, pays for both);
    # spec_k=3 exercises multi-token drafts AND a non-power-of-two cap
    # bucketing into W=4
    _assert_parity(net, ref_run, (3, 2))


@pytest.mark.slow
def test_speculative_bitwise_parity_k4_k8(net, ref_run):
    # spec_k∈{4,8} add the W=8/W=16 verify graphs (~18 s of XLA on one
    # core) — same gate, budgeted outside tier-1 like the chaos
    # scenarios
    _assert_parity(net, ref_run, (8, 4))


def test_speculative_mid_stream_fallback_stays_bitwise(net, ref_run):
    """Drafts that always miss: acceptance collapses, the per-sequence
    cooldown disables drafting mid-stream, and the stream STAYS bitwise
    the plain one (fallback is a scheduling change, never an output
    change)."""
    _, ref = ref_run

    class _AlwaysWrong(DraftSource):
        def propose(self, context, k):
            if k <= 0:
                return []
            # one draft per boundary, guaranteed != the greedy argmax:
            # the reference stream says what comes after this exact
            # context, so propose something else
            key = tuple(context)
            for p, gen in ref.items():
                full = list(p) + gen
                for i in range(len(p), len(full)):
                    if tuple(full[:i]) == key:
                        return [(full[i] + 1) % _VOCAB]
            return []

    eng = _engine(net, spec_decode=True, spec_k=2)
    b = ContinuousBatcher(eng)
    b.draft = _AlwaysWrong()
    for p in _PROMPTS:
        b.submit(Request(list(p), max_new_tokens=_MAX_NEW))
    b.run()
    outs = {tuple(r.tokens): list(r.generated) for r in b.finished}
    assert outs == ref
    st = b.stats()
    # every draft missed...
    assert st["spec_accept_rate"] == 0.0 and b.spec_drafted > 0
    # ...so the cooldown engaged: some boundaries ran the plain graph
    # (verify boundaries bump both counters, plain ones decode only)
    assert b.verify_steps < b.decode_steps
    assert eng.stats["compiles_after_warmup"] == 0
    assert eng.cache.check_leaks()


def test_spec_kill_switch_and_config_guards(net, warm):
    os.environ["MXTPU_SPEC_DECODE"] = "0"
    try:
        eng = _engine(net)
        assert eng.spec_decode is False
        b = ContinuousBatcher(eng)
        assert b.speculative is False
        # a speculative batcher over a non-speculative engine is a
        # typed config error, not a silent retrace at the first verify
        with pytest.raises(MXNetError):
            ContinuousBatcher(eng, speculative=True)
    finally:
        os.environ.pop("MXTPU_SPEC_DECODE", None)
    # greedy-only: sampling + verification argmax cannot both hold
    with pytest.raises(NotSupportedError):
        InferenceEngine(net, max_batch=3, block_size=8, max_context=16,
                        temperature=0.7, spec_decode=True,
                        compile_cache=_CC)
    # spec_k bounds: engine floor, batcher within compiled widths
    with pytest.raises(MXNetError):
        InferenceEngine(net, max_batch=3, block_size=8, max_context=16,
                        spec_decode=True, spec_k=0, compile_cache=_CC)
    eng = _engine(net, spec_decode=True, spec_k=2)
    with pytest.raises(MXNetError):
        ContinuousBatcher(eng, spec_k=4)   # exceeds compiled widths


# ----------------------------------------------------------------------
# verify() semantics at the engine seam
# ----------------------------------------------------------------------

def test_verify_single_token_rows_match_plain_decode(net, warm):
    """A verify dispatch whose rows carry ONE token each (no drafts) is
    exactly a plain decode step — the mixed-batch contract."""
    eng = _engine(net, spec_decode=True, spec_k=2)
    tok, _ = eng.prefill("a", [1, 2, 3, 1, 2])
    pos = 5
    assert eng.reserve("a", pos)
    out = eng.verify([("a", [int(tok)], pos)])
    eng.release("a")
    # replay plainly on a fresh slot: the same token must come out
    tok2, _ = eng.prefill("b", [1, 2, 3, 1, 2])
    assert int(tok2) == int(tok)
    assert eng.reserve("b", pos)
    nxt, _lg = eng.decode([("b", int(tok2), pos)])
    eng.release("b")
    assert int(out[0, 0]) == int(nxt[0])
    assert eng.stats["compiles_after_warmup"] == 0
    assert eng.cache.check_leaks()


# ----------------------------------------------------------------------
# the PrefixCache draft source: trie continuation
# ----------------------------------------------------------------------

def _pc(block_size=4, num_blocks=16):
    c = PagedKVCache(num_layers=1, num_kv_heads=2, head_dim=8,
                     num_blocks=num_blocks, block_size=block_size,
                     max_batch=2)
    return c, PrefixCache(c)


def test_continuation_walks_chain_and_partial_tail():
    c, pc = _pc()
    toks = [1, 2, 3, 4, 5, 6, 7, 8, 9, 10]   # 2 full blocks + partial
    assert c.alloc("a", len(toks))
    pc.insert("a", toks)
    # exact-prefix continuation through full blocks into the partial
    assert pc.continuation([1, 2, 3, 4], 6) == [5, 6, 7, 8, 9, 10]
    # mid-block prefix: the child block's tokens complete it
    assert pc.continuation([1, 2, 3, 4, 5, 6], 4) == [7, 8, 9, 10]
    # k caps the draft
    assert pc.continuation([1, 2, 3, 4], 3) == [5, 6, 7]
    # the partial tail is a LEAF: the walk stops there
    assert pc.continuation([1, 2, 3, 4, 5, 6, 7, 8, 9], 4) == [10]
    assert pc.continuation(toks, 4) == []
    # unknown prefix: nothing
    assert pc.continuation([9, 9, 9, 9], 4) == []
    c.free("a")
    pc.clear()
    assert c.check_leaks()


def test_continuation_is_refcount_and_lru_neutral():
    c, pc = _pc()
    toks = [1, 2, 3, 4, 5, 6]
    assert c.alloc("a", len(toks))
    pc.insert("a", toks)
    refs_before = {b: c.refcount(b) for b in c.table("a")}
    tick = pc._tick
    lookups, hits = pc.lookups, pc.hits
    assert pc.continuation([1, 2, 3, 4], 2) == [5, 6]
    # a draft is a guess, not an adoption: no refs, no LRU churn, no
    # hit accounting (eviction pressure must not see phantom traffic)
    assert {b: c.refcount(b) for b in c.table("a")} == refs_before
    assert pc._tick == tick
    assert (pc.lookups, pc.hits) == (lookups, hits)
    c.free("a")
    pc.clear()
    assert c.check_leaks()


def test_continuation_after_eviction_degrades_to_no_draft():
    c, pc = _pc()
    toks = [1, 2, 3, 4, 5, 6, 7, 8]
    assert c.alloc("a", len(toks))
    pc.insert("a", toks)
    c.free("a")                      # only the chain holds the blocks
    drafted = pc.continuation([1, 2, 3, 4], 4)
    assert drafted == [5, 6, 7, 8]
    # chain evicted mid-draft: the already-returned ints stay valid
    # (a wrong guess just fails acceptance) and a NEW walk finds
    # nothing — no draft, never a crash
    assert pc.evict(blocks_needed=c.num_blocks) > 0
    assert drafted == [5, 6, 7, 8]
    assert pc.continuation([1, 2, 3, 4], 4) == []
    assert pc.held_blocks() == 0
    assert c.check_leaks()


def test_draft_source_prefers_cache_then_ngram():
    c, pc = _pc()
    toks = [1, 2, 3, 4, 5, 6]
    assert c.alloc("a", len(toks))
    pc.insert("a", toks)
    ds = DraftSource(prefix_cache=pc)
    # cache hit: the trie continuation wins
    assert ds.propose([1, 2, 3, 4], 2) == [5, 6]
    assert ds.from_cache == 1 and ds.from_ngram == 0
    # cache miss, self-repeating context: prompt-lookup n-gram fires
    assert ds.propose([7, 8, 9, 7, 8, 9, 7, 8], 3) == [9, 7, 8]
    assert ds.from_ngram == 1
    # nothing to match: no draft
    assert ds.propose([11, 12, 13], 4) == []
    assert ds.propose([5], 4) == []          # too short
    assert ds.propose([7, 8, 9, 7, 8], 0) == []
    c.free("a")
    pc.clear()
    assert c.check_leaks()


def test_ngram_longest_gram_and_recency_win():
    ds = DraftSource()
    # trailing [1,2] occurs twice earlier; the MOST RECENT occurrence
    # (index 3) supplies the continuation [9], not index 0's [5]
    assert ds.propose([1, 2, 5, 1, 2, 9, 1, 2], 1) == [9]
    # the longest matching gram wins: trailing [2,3] matches before
    # the shorter trailing [3] gets a chance
    assert ds.propose([1, 2, 3, 7, 3, 2, 3], 1) == [7]
