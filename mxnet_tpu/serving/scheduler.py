"""Continuous-batching request scheduler over the compiled engine.

The serving loop the north star asks for: requests arrive on a queue,
new sequences JOIN the running decode batch at token boundaries and
finished ones vacate their slot in the same boundary — the decode batch
never drains to admit work (continuous batching), unlike the static
discipline where a batch is formed once and every slot waits for the
slowest member.

Prefill/decode split: prompts run through the engine's bucketed prefill
graphs as separate calls BETWEEN decode steps (at most
``prefills_per_step`` per boundary, so one long prompt delays the
running batch by a bounded amount instead of stalling it for a whole
generation).  ``StaticBatcher`` implements the fixed-batch baseline over
the SAME engine so the load generator's continuous-vs-static comparison
measures the scheduling policy, not two different compiled paths.

Chunked prefill (ISSUE 12): when the engine's ``prefill_chunk`` is set,
admission switches from one-prompt-per-dispatch to PACKED chunks — every
boundary gathers up to ``max_batch`` rows (tail chunks of in-flight long
prompts first, then new admissions, each first consulting the prefix
cache so a cached system prompt costs zero compute) into ONE
``chunk_prefill`` dispatch.  Same work, strictly fewer dispatches than
the one-per-boundary policy on any mixed queue — the deterministic gate
in tests/test_serving_frontend.py.  A long prompt still delays the
running batch by at most one chunk per boundary.

Speculative decoding (ISSUE 17): with the engine's ``spec_decode`` set,
the decode boundary becomes draft -> verify -> accept.  A model-free
:class:`~.draft.DraftSource` proposes up to ``spec_k`` continuation
tokens per row (prefix-cache trie walk, then prompt-lookup n-gram); ONE
``engine.verify`` dispatch scores every row's last committed token plus
its drafts; the greedy-matching draft prefix is committed (1..K+1
tokens per boundary from one dispatch) and the paged-KV write-ahead
past the committed length is trimmed.  Acceptance is exact token
equality against the verify argmaxes, so the committed stream is
BITWISE the non-speculative greedy stream — speculation changes
dispatch count, never output (tests/test_spec_decode.py).  A sequence
whose drafts keep missing stops drafting for a cooldown window
(per-sequence fallback — it rides the same dispatch as a plain 1-token
row), and a boundary where no row drafts runs the plain decode graph.

Disaggregated prefill/decode (ISSUE 18): a batcher can be built with a
``role`` — ``"prefill"`` admits prompts and parks the finished-prefill
requests in a ``handoff_ready`` outbox instead of decoding them;
``"decode"`` never admits from its queue and instead calls ``adopt_handoff`` on
requests whose KV blocks were filled by a prefill-role peer over the
SAME :class:`~.kv_cache.PagedKVCache`.  The handoff rides the CoW
refcount machinery: the decode side refs every block FIRST (adopt), the
prefill side releases its slot SECOND (``complete_handoff``, which
insists every block still shows the adopter's hold) — a crash between
the two leaves blocks over-held (requeue-able), never freed early.
Engines over a shared pool namespace their slots (``slot_ns``) so slot
keys cannot collide.  Protocol violations raise the typed
:class:`~.kv_cache.HandoffError`.

Everything here is host-side policy: per-token device work is exactly
one compiled decode step; the only host pull per boundary is the sampled
token vector (needed to detect EOS and admit/evict — the serving
analogue of HB10's one-sync-per-window rule).
"""
from __future__ import annotations

import itertools
import time
from collections import deque

from ..base import MXNetError
from .. import telemetry as _telem
from ..telemetry import tracing as _trace
from ..telemetry import watchdog as _watchdog
from .draft import DraftSource
from .kv_cache import HandoffError

__all__ = ["Request", "ContinuousBatcher", "StaticBatcher"]

_ids = itertools.count()


class Request:
    """One generation request: ``tokens`` (prompt ids), ``max_new_tokens``
    and an optional per-request ``eos_id``."""

    def __init__(self, tokens, max_new_tokens, eos_id=None, request_id=None):
        self.id = next(_ids) if request_id is None else request_id
        self.tokens = [int(t) for t in tokens]
        if not self.tokens:
            raise MXNetError("Request needs at least one prompt token")
        self.max_new_tokens = int(max_new_tokens)
        self.eos_id = eos_id
        # lifecycle stamps (perf_counter seconds) + outputs
        self.submit_t = None
        self.first_token_t = None
        self.finish_t = None
        self.generated = []
        self.finish_reason = None     # "eos" | "length"
        # causal tracing (ISSUE 14): the root span of this request's
        # life — created at first admission, SURVIVES a drain/requeue
        # hop (the requeued chain parents under the same root)
        self.trace = None
        self._queue_t0 = None         # current queue-residency start

    @property
    def done(self):
        return self.finish_reason is not None

    def latency(self):
        if self.submit_t is None or self.finish_t is None:
            return None
        return self.finish_t - self.submit_t

    def ttft(self):
        """Time to first token."""
        if self.submit_t is None or self.first_token_t is None:
            return None
        return self.first_token_t - self.submit_t

    def tpot(self):
        """Time per output token AFTER the first (the decode-pool
        latency signal the autoscaler scales on; None until a second
        token exists to measure)."""
        if (self.first_token_t is None or self.finish_t is None
                or len(self.generated) < 2):
            return None
        return (self.finish_t - self.first_token_t) \
            / (len(self.generated) - 1)


class _BatcherBase:
    def __init__(self, engine, slot_ns=None, role="combined"):
        if role not in ("combined", "prefill", "decode"):
            raise MXNetError(f"batcher role {role!r} must be "
                             "combined|prefill|decode")
        self.engine = engine
        # slot namespace: engines sharing one PagedKVCache (the
        # disaggregated fleet) must not collide on slot keys — slots
        # are opaque hashables, so a namespaced slot is (ns, i)
        self.slot_ns = slot_ns
        self.role = role
        # prefill-role outbox: requests whose prompt is fully cached in
        # a slot THIS batcher still owns, awaiting block handoff to a
        # decode-role peer (the router drains it every boundary)
        self.handoff_ready = deque()
        self.queue = deque()
        self.finished = []
        # per-boundary occupancy samples: active slots / max_batch
        self.occupancy_samples = []
        self.decode_steps = 0
        self.tokens_generated = 0
        # speculative accounting (stays zero on non-speculative runs)
        self.verify_steps = 0
        self.spec_drafted = 0
        self.spec_accepted = 0

    def submit(self, request):
        request.submit_t = time.perf_counter()
        if _trace.enabled():
            if request.trace is None:
                request.trace = _trace.start("request", id=request.id)
            request._queue_t0 = _trace.clock()
        self.queue.append(request)
        return request

    # -- shared helpers --------------------------------------------------

    def _admit_one(self, slot, req):
        """Prefill ``req`` into ``slot``; returns True on admission.
        The first generated token comes from the prefill itself."""
        tp0 = _trace.clock() if _trace.enabled() else None
        out = self.engine.prefill(slot, req.tokens)
        if out is None:
            return False
        if tp0 is not None:
            # admission succeeded: queue residency ends where the
            # prefill begins; both parent under the request root
            if req._queue_t0 is not None:
                _trace.record("queue", req._queue_t0, tp0,
                              parent=req.trace)
                req._queue_t0 = None
            _trace.record("prefill", tp0, _trace.clock(),
                          parent=req.trace, slot=slot,
                          tokens=len(req.tokens))
        tok, _logits = out
        req.first_token_t = time.perf_counter()
        if _telem.enabled() and req.submit_t is not None:
            _telem.observe("serving.ttft_ms",
                           (req.first_token_t - req.submit_t) * 1e3)
        self._append_token(req, slot, tok)
        return True

    def _append_token(self, req, slot, tok):
        req.generated.append(int(tok))
        self.tokens_generated += 1
        _telem.inc("serving.tokens_generated")
        if req.eos_id is not None and int(tok) == int(req.eos_id):
            req.finish_reason = "eos"
        elif len(req.generated) >= req.max_new_tokens:
            req.finish_reason = "length"
        if req.done:
            req.finish_t = time.perf_counter()
            self.engine.release(slot)
            self.finished.append(req)
            _trace.finish(req.trace, reason=req.finish_reason,
                          tokens=len(req.generated))
            if _telem.enabled():
                _telem.inc("serving.requests_finished")
                lat = req.latency()
                if lat is not None:
                    _telem.observe("serving.request_latency_ms",
                                   lat * 1e3)

    def _decode_active(self, active):
        """One joined decode step over ``active`` {slot: request}."""
        td0 = _trace.clock() if _trace.enabled() else None
        entries = []
        for slot, req in active.items():
            pos = len(req.tokens) + len(req.generated) - 1
            # the token AT ``pos`` is the last generated one; its K/V is
            # written by this step, so the table must cover ``pos``
            if not self.engine.reserve(slot, pos):
                raise MXNetError("KV pool exhausted mid-decode; raise "
                                 "num_blocks or lower max_batch")
            entries.append((slot, req.generated[-1], pos))
        nxt, _logits = self.engine.decode(entries)
        self.decode_steps += 1
        self.occupancy_samples.append(len(entries) / self.engine.max_batch)
        if td0 is not None:
            # one joined dispatch, one span PER REQUEST (same [t0,t1],
            # each parented under its own request root): every request's
            # chain carries all N of its decode boundaries
            td1 = _trace.clock()
            for slot, _t, pos in entries:
                _trace.record("decode", td0, td1,
                              parent=active[slot].trace, pos=pos)
        if _telem.enabled():
            # per-boundary scheduler state: what a live scrape of a
            # serving pod needs to spot admission stalls (ISSUE 9)
            _telem.set_gauge("serving.queue_depth", len(self.queue))
            _telem.observe("serving.batch_occupancy",
                           len(entries) / self.engine.max_batch,
                           edges=(0.125, 0.25, 0.375, 0.5, 0.625, 0.75,
                                  0.875, 1.0))
            _telem.inc("serving.decode_steps")
        if _watchdog.enabled():
            # the serving health rules tick at the same boundary seam
            # (host ints only — queue saturation + KV-leak trend)
            _watchdog.on_serving_boundary(
                queue_depth=len(self.queue),
                kv_blocks_in_use=self.engine.cache.blocks_in_use)
        for (slot, _t, _p), tok in zip(entries, nxt):
            self._append_token(active[slot], slot, tok)
        for slot in [s for s, r in active.items() if r.done]:
            del active[slot]

    def occupancy(self):
        s = self.occupancy_samples
        return sum(s) / len(s) if s else None

    def stats(self):
        lat = sorted(r.latency() for r in self.finished
                     if r.latency() is not None)

        def pct(p):
            if not lat:
                return None
            return lat[min(len(lat) - 1, int(round(p * (len(lat) - 1))))]

        return {"requests": len(self.finished),
                "tokens_generated": self.tokens_generated,
                "decode_steps": self.decode_steps,
                "verify_steps": self.verify_steps,
                "spec_accept_rate": (
                    round(self.spec_accepted / self.spec_drafted, 4)
                    if self.spec_drafted else None),
                "tokens_per_dispatch": (
                    round(self.tokens_generated / self.decode_steps, 4)
                    if self.decode_steps else None),
                "occupancy": (round(self.occupancy(), 4)
                              if self.occupancy() is not None else None),
                "p50_latency_s": pct(0.50), "p99_latency_s": pct(0.99),
                "cache": self.engine.cache.stats()}


class _PrefillState:
    """A prompt part-way through chunked prefill: ``done`` positions of
    ``req.tokens`` are cached in ``slot`` (prefix-cache hits count)."""

    __slots__ = ("req", "slot", "done")

    def __init__(self, req, slot, done):
        self.req = req
        self.slot = slot
        self.done = int(done)


class ContinuousBatcher(_BatcherBase):
    """Token-boundary continuous batching: admit into free slots before
    every decode step, evict finished sequences the moment EOS/length
    hits, never drain the batch to take new work.  With the engine's
    ``prefill_chunk`` set, admission packs chunks from several prompts
    into one dispatch per boundary (ISSUE 12 chunked prefill)."""

    # boundaries a sequence sits out after its drafts stop landing
    # (deterministic host counter; re-probes when it expires)
    _spec_cooldown = 8
    _spec_miss_limit = 2

    def __init__(self, engine, prefills_per_step=1, speculative=None,
                 spec_k=None, slot_ns=None, role="combined"):
        super().__init__(engine, slot_ns=slot_ns, role=role)
        self.prefills_per_step = int(prefills_per_step)
        self.active = {}          # slot -> Request
        self.prefilling = {}      # slot -> _PrefillState (chunked only)
        self._free_slots = [self._slot(i)
                            for i in range(engine.max_batch - 1, -1, -1)]
        # speculative decoding (ISSUE 17): defaults follow the engine
        # (which reads MXTPU_SPEC_DECODE / MXTPU_SPEC_K)
        self.speculative = engine.spec_decode if speculative is None \
            else bool(speculative)
        if self.speculative and not engine.spec_decode:
            raise MXNetError(
                "speculative batching needs an engine built with "
                "spec_decode=True (the verify graphs compile at warmup)")
        self.spec_k = engine.spec_k if spec_k is None else int(spec_k)
        if not 1 <= self.spec_k <= engine.spec_k:
            raise MXNetError(f"spec_k {self.spec_k} outside the "
                             f"engine's compiled [1, {engine.spec_k}]")
        self.draft = DraftSource(prefix_cache=engine.prefix_cache)
        self._spec_state = {}     # req.id -> [misses, cooldown]

    def _slot(self, i):
        """Slot keys are opaque hashables end-to-end (engine, cache,
        traces); a namespaced batcher mints ``(ns, i)`` so two engines
        over one SHARED pool can never collide."""
        return i if self.slot_ns is None else (self.slot_ns, i)

    def _stage_or_activate(self, slot, req):
        """A freshly prefilled, unfinished request either joins the
        decode batch (combined role) or parks in the handoff outbox —
        the slot and its blocks stay owned by THIS batcher until a
        decode-role peer adopts them (adopt-then-release)."""
        if self.role == "prefill":
            self.handoff_ready.append((slot, req))
        else:
            self.active[slot] = req

    def adopt_handoff(self, req, blocks, n_tokens):
        """Decode-role entry seam: adopt a prefilled request whose KV
        ``blocks`` (covering ``n_tokens`` positions) live in the SHARED
        pool.  Each block gains a holder BEFORE the prefill side drops
        its own (the adopt-then-release protocol — a crash between the
        two leaves blocks over-held, never freed early).  Returns the
        new slot, or None when no batch slot is free (backpressure:
        the entry stays in the prefill outbox)."""
        if self.role != "decode":
            raise HandoffError(
                f"adopt_handoff on a {self.role!r}-role batcher — only "
                "decode-role replicas adopt prefill handoffs")
        if not self._free_slots:
            return None
        slot = self._free_slots[-1]
        self.engine.cache.adopt(slot, blocks, n_tokens)
        self._free_slots.pop()
        self.active[slot] = req
        return slot

    def complete_handoff(self, slot):
        """Prefill-role exit seam: release ``slot`` AFTER the decode
        side adopted its blocks.  Every block must still show the
        adopter's hold (refcount >= 2) — releasing sole-held blocks
        here would free live KV mid-handoff, the exact leak class the
        typed error names."""
        cache = self.engine.cache
        for blk in cache.table(slot):
            if cache.refcount(blk) < 2:
                raise HandoffError(
                    f"complete_handoff({slot!r}): block {blk} has "
                    f"{cache.refcount(blk)} holder(s) — the decode side "
                    "must adopt before the prefill side releases")
        self.engine.release(slot)
        self._free_slots.append(slot)

    def step(self):
        """One scheduling boundary: admit queued requests (one packed
        chunk dispatch when chunked, else up to ``prefills_per_step``
        single-prompt prefills), then run one joined decode step.
        Returns the amount of work done — admissions + prefill rows +
        sequences decoded (0 means the boundary was a no-op)."""
        if self.engine.prefill_chunk:
            admitted = self._admit_chunked()
        else:
            admitted = self._admit_serial()
        if self.role == "prefill":
            # the prefill pool's saturation signal: admissions this
            # boundary + prompts mid-chunk, over the batch (TTFT
            # pressure makes the autoscaler grow THIS pool)
            self.occupancy_samples.append(min(
                1.0, (admitted + len(self.prefilling))
                / self.engine.max_batch))
            return admitted
        if not self.active:
            return admitted
        before = set(self.active)
        if self.speculative:
            self._decode_spec(self.active)
        else:
            self._decode_active(self.active)
        for slot in before - set(self.active):
            self._free_slots.append(slot)
        return admitted + len(before)

    def _decode_spec(self, active):
        """One speculative boundary: draft, verify in ONE dispatch,
        commit the greedy-matching prefix, trim the write-ahead.

        Bitwise contract: a committed token is either a verify argmax
        (computed by the decode body op-for-op) or a draft that EQUALED
        one — so the generated stream is exactly the plain greedy
        stream, only produced in fewer dispatches.  A boundary where no
        row drafts (cold caches, cooldowns, length caps) delegates to
        the plain decode graph."""
        eng = self.engine
        drafts = {}
        any_draft = False
        for slot, req in active.items():
            pos = len(req.tokens) + len(req.generated) - 1
            st = self._spec_state.get(req.id)
            if st is not None and st[1] > 0:
                st[1] -= 1        # cooling down: ride as a plain row
                drafts[slot] = []
                continue
            # a draft may commit up to cap+1 tokens and write K/V up to
            # pos+cap; both the length budget and the context ceiling
            # (next boundary writes pos+committed) bound the window
            cap = min(self.spec_k,
                      req.max_new_tokens - len(req.generated) - 1,
                      eng.max_context - 2 - pos)
            d = self.draft.propose(req.tokens + req.generated, cap) \
                if cap > 0 else []
            drafts[slot] = d
            if d:
                any_draft = True
        if not any_draft:
            return self._decode_active(active)
        td0 = _trace.clock() if _trace.enabled() else None
        entries = []
        for slot, req in active.items():
            pos = len(req.tokens) + len(req.generated) - 1
            toks = [req.generated[-1]] + drafts[slot]
            if len(toks) > 1 and not eng.reserve(slot, pos, len(toks)):
                toks = toks[:1]   # pool pressure: shed the write-ahead
            if len(toks) == 1 and not eng.reserve(slot, pos):
                raise MXNetError("KV pool exhausted mid-decode; raise "
                                 "num_blocks or lower max_batch")
            entries.append((slot, toks, pos))
        out = eng.verify(entries)
        self.decode_steps += 1
        self.verify_steps += 1
        self.occupancy_samples.append(len(entries) / eng.max_batch)
        td1 = _trace.clock() if td0 is not None else None
        for i, (slot, toks, pos) in enumerate(entries):
            req = active[slot]
            D = len(toks) - 1
            # out[i, j] = greedy token after absorbing toks[:j+1]; the
            # drafts matching their predecessor's argmax are accepted,
            # each match's own argmax rides along as the next commit
            committed = [int(out[i, 0])]
            j = 1
            while j <= D and int(toks[j]) == int(out[i, j - 1]):
                committed.append(int(out[i, j]))
                j += 1
            accepted = j - 1
            self.spec_drafted += D
            self.spec_accepted += accepted
            if D:
                st = self._spec_state.setdefault(req.id, [0, 0])
                if accepted == 0:
                    st[0] += 1
                    if st[0] >= self._spec_miss_limit:
                        st[0], st[1] = 0, self._spec_cooldown
                else:
                    st[0] = 0
            if td0 is not None:
                _trace.record("verify", td0, td1, parent=req.trace,
                              pos=pos, drafted=D, accepted=accepted)
            for tok in committed:
                if req.done:
                    break         # EOS inside the window: rest is moot
                self._append_token(req, slot, tok)
            if req.done:
                self._spec_state.pop(req.id, None)
            else:
                # roll back the write-ahead: K/V past the committed
                # length is rejected-draft garbage — drop the length
                # and any blocks only the garbage covered
                n = pos + 1 + accepted
                eng.cache.trim(slot, n)
                eng.cache.set_len(slot, n)
        if _telem.enabled():
            _telem.set_gauge("serving.queue_depth", len(self.queue))
            _telem.observe("serving.batch_occupancy",
                           len(entries) / eng.max_batch,
                           edges=(0.125, 0.25, 0.375, 0.5, 0.625, 0.75,
                                  0.875, 1.0))
            _telem.inc("serving.decode_steps")
            if self.spec_drafted:
                _telem.set_gauge(
                    "serving.spec_accept_rate",
                    round(self.spec_accepted / self.spec_drafted, 4))
        if _watchdog.enabled():
            _watchdog.on_serving_boundary(
                queue_depth=len(self.queue),
                kv_blocks_in_use=eng.cache.blocks_in_use)
        for slot in [s for s, r in active.items() if r.done]:
            del active[slot]

    def _admit_serial(self):
        admitted = 0
        while (self.queue and self._free_slots
               and admitted < self.prefills_per_step):
            slot = self._free_slots[-1]
            req = self.queue[0]
            if not self._admit_one(slot, req):
                break                       # pool full / prompt too long
            self.queue.popleft()
            self._free_slots.pop()
            admitted += 1
            if req.done:                    # finished inside prefill
                self._free_slots.append(slot)
            else:
                self._stage_or_activate(slot, req)
        return admitted

    def _admit_chunked(self):
        """Pack one ``chunk_prefill`` dispatch: tail chunks of in-flight
        prompts first (they hold slots and blocks — finish them), then
        new admissions through the prefix cache.  Returns admissions +
        dispatched rows."""
        eng = self.engine
        C = eng.prefill_chunk
        entries, rows = [], {}
        for slot, st in list(self.prefilling.items()):
            if len(entries) >= eng.max_batch:
                break
            chunk = st.req.tokens[st.done:st.done + C]
            entries.append((slot, chunk, st.done))
            rows[slot] = st
        admitted = 0
        while (self.queue and self._free_slots
               and len(entries) < eng.max_batch):
            req = self.queue[0]
            if len(req.tokens) - 1 >= eng.max_context:
                raise MXNetError(
                    "request cannot be admitted (prompt exceeds "
                    "max_context)")
            slot = self._free_slots[-1]
            start = eng.attach_prefix(slot, req.tokens)
            if start == 0 and not eng.cache.alloc(slot, 0):
                break                       # cannot even open a table
            self.queue.popleft()
            self._free_slots.pop()
            if _trace.enabled() and req._queue_t0 is not None:
                _trace.record("queue", req._queue_t0, _trace.clock(),
                              parent=req.trace, prefix_hit=start)
                req._queue_t0 = None
            st = _PrefillState(req, slot, start)
            self.prefilling[slot] = st
            entries.append((slot, req.tokens[start:start + C], start))
            rows[slot] = st
            admitted += 1
        if not entries:
            return admitted
        tc0 = _trace.clock() if _trace.enabled() else None
        out = eng.chunk_prefill(entries)
        if out is None and eng.prefix_cache is not None:
            # pool pressure: evict LRU chains no request still shares
            # (refcount > 1 blocks survive untouched), then retry once
            need = sum(
                max(0, eng.cache.blocks_for(start + len(chunk))
                    - len(eng.cache.table(slot)))
                for slot, chunk, start in entries)
            if eng.prefix_cache.evict(blocks_needed=need):
                out = eng.chunk_prefill(entries)
        if out is None:
            # still starved: in-flight prompts keep their state and
            # retry next boundary (decode frees blocks as requests end)
            return admitted
        nxt, _logits = out
        if tc0 is not None:
            # one packed dispatch, one span per packed ROW — each
            # chunk parents under its own request's root
            tc1 = _trace.clock()
            for slot, chunk, start in entries:
                _trace.record("prefill_chunk", tc0, tc1,
                              parent=rows[slot].req.trace, slot=slot,
                              start=start, tokens=len(chunk))
        for i, (slot, chunk, start) in enumerate(entries):
            st = rows[slot]
            st.done = start + len(chunk)
            if st.done < len(st.req.tokens):
                continue                    # more chunks to come
            del self.prefilling[slot]
            req = st.req
            req.first_token_t = time.perf_counter()
            if _telem.enabled() and req.submit_t is not None:
                _telem.observe("serving.ttft_ms",
                               (req.first_token_t - req.submit_t) * 1e3)
            # register the finished prompt BEFORE decode writes past it
            # (the partial tail block CoW-forks on the first write)
            eng.insert_prefix(slot, req.tokens)
            self._append_token(req, slot, int(nxt[i]))
            if req.done:
                self._free_slots.append(slot)
            else:
                self._stage_or_activate(slot, req)
        return admitted + len(entries)

    def run(self, max_steps=100000):
        """Drive until queue and batch are empty."""
        if self.role != "combined":
            raise MXNetError(
                f"run() drives a combined-role batcher; a "
                f"{self.role!r}-role batcher only makes progress under "
                "a Router that drains its handoffs")
        steps = 0
        while self.queue or self.active or self.prefilling:
            moved = self.step()
            steps += 1
            if steps > max_steps:
                raise MXNetError("run() exceeded max_steps — scheduler "
                                 "stuck (pool too small for any "
                                 "queued request?)")
            if moved == 0 and not self.active and \
                    (self.queue or self.prefilling):
                # a no-op boundary with work still queued: the head
                # request can never be admitted
                raise MXNetError(
                    "request cannot be admitted (prompt exceeds "
                    "max_context or KV pool too small)")
        return self.stats()


class StaticBatcher(_BatcherBase):
    """The fixed-batch baseline: form a batch of up to ``max_batch``
    requests, prefill them all, decode until EVERY member finishes
    (finished slots idle — their decode rows are wasted), then form the
    next batch.  Same engine, same graphs; only the policy differs."""

    def run(self, max_steps=100000):
        steps = 0
        while self.queue:
            n_before = len(self.queue)
            active = {}
            for slot in range(self.engine.max_batch):
                if not self.queue:
                    break
                req = self.queue[0]
                if not self._admit_one(slot, req):
                    break
                self.queue.popleft()
                if not req.done:
                    active[slot] = req
            if len(self.queue) == n_before:
                # nothing could be admitted into an EMPTY batch: the
                # head request can never run
                raise MXNetError(
                    "request cannot be admitted (prompt exceeds "
                    "max_context or KV pool too small)")
            while active:
                # occupancy decays as members finish: the finished
                # slots' rows ride every remaining decode step unused —
                # the waste continuous batching exists to reclaim
                self._decode_active(active)
                steps += 1
                if steps > max_steps:
                    raise MXNetError("static run exceeded max_steps")
        return self.stats()
