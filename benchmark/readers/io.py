"""Layer metrics of the input pipeline, from the program's own spans
(``mxnet_tpu/telemetry/tracing.py``).  ``DevicePrefetcher``'s worker thread
leaves one trace a batch::

    io.batch (batch=n)
      io.decode                 the time in next(source)
        io.rec.fetch            blocked on the decode pool
        io.rec.augment          the host's passes over the decoded batch
        io.rec.stage            array(...): cast, and put to the device
      io.h2d                    the put onto the mesh

and the consumer thread one ``io.wait`` (``batch``, ``queued``) a batch, beside
the ``train.step`` root that consumes it.  The host metrics read durations on
the host clock (``t0`` / ``t1``, the clock of ``ctx.t_start`` / ``ctx.t_end``);
``idle_in_wait_pct`` lays the consumer thread's spans over the device trace by
``t0_ns`` / ``t1_ns``, the way ``readers/spans.py`` does for the Gluon loop
(its clock helpers are used here; what differs is which spans count: only the
consumer thread's, because the worker's run beside them).  A program without
these spans gives ``None`` everywhere.
"""
import statistics
from trace import OPS, clip, spans as intervals, subtract, total, union

from readers.spans import MAX_OVERHANG_NS, clock_overhang_ns, session_starts

STEP, WAIT, BATCH, DECODE = "train.step", "io.wait", "io.batch", "io.decode"
FETCH, AUGMENT, STAGE, H2D = ("io.rec.fetch", "io.rec.augment",
                              "io.rec.stage", "io.h2d")


def _in_window(ctx, name):
    return [s for s in ctx.spans if s["name"] == name
            and s["t0"] >= ctx.t_start and s["t1"] <= ctx.t_end]


def _mean_ms(spans):
    if not spans:
        return None
    return sum(s["t1"] - s["t0"] for s in spans) / len(spans) * 1e3


def _median(values):
    return statistics.median(values) if values else None


def wait_ms(ctx):
    """Time a step waited for data: the window's ``io.wait`` summed, over
    its ``train.step`` roots."""
    steps, waits = _in_window(ctx, STEP), _in_window(ctx, WAIT)
    if not steps or not waits:
        return None
    _note_period(ctx)
    return sum(s["t1"] - s["t0"] for s in waits) / len(steps) * 1e3


def fetch_ms(ctx):
    """Mean ``io.rec.fetch``: the worker blocked on the decode pool."""
    return _mean_ms(_in_window(ctx, FETCH))


def augment_ms(ctx):
    """Mean ``io.rec.augment``: the host's passes over a decoded batch."""
    return _mean_ms(_in_window(ctx, AUGMENT))


def h2d_ms(ctx):
    """Mean ``io.rec.stage`` + mean ``io.h2d``: making the device array."""
    stage, h2d = _in_window(ctx, STAGE), _in_window(ctx, H2D)
    if not stage or not h2d:
        return None
    augment = _in_window(ctx, AUGMENT)
    ctx.note("io.bytes", {
        "augment_out": augment[-1]["args"] if augment else None,
        "stage": stage[-1]["args"], "h2d": h2d[-1]["args"]})
    return _mean_ms(stage) + _mean_ms(h2d)


def _note_period(ctx):
    """Whether the spans close: the three stages beside ``io.batch``'s mean,
    the worker's period (start of one ``io.batch`` to the next) and the
    window's median step (start of one ``train.step`` to the next), ms."""
    batches = _in_window(ctx, BATCH)
    if not batches:
        return
    starts = sorted(s["t0"] for s in batches)
    steps = sorted(s["t0"] for s in _in_window(ctx, STEP))
    fetches = _in_window(ctx, FETCH)
    waits = _in_window(ctx, WAIT)
    pool = {k: sum(s["args"].get(k, 0) for s in fetches) / len(fetches) / 1e6
            for k in ("busy_ns", "full_ns")} if fetches else {}
    ctx.note("io.period", {
        "fetch_ms": _mean_ms(fetches),
        "augment_ms": _mean_ms(_in_window(ctx, AUGMENT)),
        "stage_ms": _mean_ms(_in_window(ctx, STAGE)),
        "h2d_ms": _mean_ms(_in_window(ctx, H2D)),
        "decode_ms": _mean_ms(_in_window(ctx, DECODE)),
        "batch_ms": _mean_ms(batches),
        "worker_period_ms_median": _median(
            [(b - a) * 1e3 for a, b in zip(starts, starts[1:])]),
        "step_ms_median": _median(
            [(b - a) * 1e3 for a, b in zip(steps, steps[1:])]),
        "batches": len(batches), "steps": len(steps),
        "waits_that_found_the_queue_empty": sum(
            1 for s in waits if s["args"].get("queued") == 0),
        "waits": len(waits),
        "pool_busy_ms_per_fetch": pool.get("busy_ns"),
        "pool_before_full_queue_ms_per_fetch": pool.get("full_ns"),
        "native": fetches[-1]["args"].get("native") if fetches else None,
        # the consumer's side: where train.step's host time goes when its
        # batch is a fresh array every step (ROADMAP D6)
        "train_phase_ms": {
            phase: _mean_ms(_in_window(ctx, "train.phase." + phase))
            for phase in ("prepare", "h2d", "dispatch", "commit")}})


def _idle_by_span(ctx):
    """``{span name: idle ns}`` of the first chip's traced window for the
    top-level spans of the consumer thread (the thread of ``train.step``),
    with ``"uncovered"`` for the idle time none of them covers and
    ``"window"`` for the window; ``None`` without a trace, without ns stamps
    or with a clock that does not fit.  Computed once a run, and noted."""
    if "_io_idle_by_span" not in ctx.__dict__:
        ctx._io_idle_by_span = table = _compute_idle_by_span(ctx)
        if table is not None:
            window = table["window"]
            ctx.note("idle_by_span", {
                "window_ms": window / 1e6,
                "idle_pct_of_window": {
                    name: 100.0 * ns / window for name, ns in
                    sorted(table.items(), key=lambda kv: -kv[1])
                    if name != "window"},
                "thread": "the consumer's: the worker's io.batch traces "
                          "run beside these spans and are not laid over",
                "inside_other_spans": ["jit.compile"]})
    return ctx._io_idle_by_span


def _compute_idle_by_span(ctx):
    if ctx.trace is None:
        return None
    d = ctx.device_ids[0]
    w = ctx.trace.window(d)
    threads = {s["thread"] for s in ctx.spans if s["name"] == STEP}
    stamped = [s for s in ctx.spans
               if s["thread"] in threads and s["parent"] is None
               and s.get("t0_ns") is not None and s.get("t1_ns") is not None]
    starts = session_starts(ctx.trace.host)
    if w is None or len(threads) != 1 or not stamped or not starts:
        return None
    start = starts[len(starts) // 2]
    # from here on a span is (name, start, end) on the trace's clock
    placed = [(s["name"], s["t0_ns"] - start, s["t1_ns"] - start)
              for s in stamped]
    dispatches = sorted((t, t + dur) for name, t, dur in ctx.trace.host
                        if name == "bench.dispatch")
    overhang = clock_overhang_ns([(t0, t1) for _, t0, t1 in placed],
                                 dispatches)
    ok = overhang is not None and overhang <= MAX_OVERHANG_NS
    ctx.note("clock", {
        "largest_overhang_us": None if overhang is None else overhang / 1e3,
        "limit_us": MAX_OVERHANG_NS / 1e3, "aligned": ok,
        "session_start_wall_ns": start,
        "session_start_spread_us": (starts[-1] - starts[0]) / 1e3,
        "wall_stamps": len(starts), "bench_dispatch": len(dispatches)})
    if not ok:
        return None
    lo, hi, _ = w
    gaps = subtract([(lo, hi)], intervals(ctx.trace.line(d, OPS)))
    table = {"window": hi - lo}
    for name, t0, t1 in placed:
        part = total(clip(gaps, t0, t1))
        if part:
            table[name] = table.get(name, 0) + part
    table["uncovered"] = total(subtract(
        gaps, union([(t0, t1) for _, t0, t1 in placed])))
    return table


def idle_in_wait_pct(ctx):
    """Share of the traced window in which the core ran nothing while the
    consumer thread was inside ``io.wait``."""
    if not any(s["name"] == WAIT for s in ctx.spans):
        return None         # no prefetcher in this program
    table = _idle_by_span(ctx)
    if table is None:
        return None
    return 100.0 * table.get(WAIT, 0) / table["window"]
