"""Layer metrics of the Gluon loop, from the program's own spans
(``mxnet_tpu/telemetry/tracing.py``): ``gluon.forward``, ``autograd.backward``
and ``gluon.update`` root spans, three or four a step on the calling thread.

The host metrics read the spans' durations on the host clock (``t0`` /
``t1``, the clock of ``ctx.t_start`` / ``ctx.t_end``).  The idle metrics lay
the spans over the device trace by ``t0_ns`` / ``t1_ns``, which the program
stamps on the clock the profiler stamps its events with (the wall clock).  The
trace's events are relative to the start of the profiler's session; the runner
leaves that start in the trace (``runners/train_gluon.py``, ``WALL_STAMP``).
The idle metrics say nothing where a span record has no ns stamp (a program
older than the stamps), where the trace has no wall-clock stamp, or where the
spans then do not fall where the benchmark's own annotations say they ran.
"""
from trace import OPS, clip, spans as intervals, subtract, total, union

FORWARD, BACKWARD, UPDATE = "gluon.forward", "autograd.backward", \
    "gluon.update"
# The profiler stamps its events on the wall clock (time.time_ns) but writes
# them relative to the start of its session, and trace.py keeps neither that
# start nor a host event that is not named ``bench.*``.  So a runner whose
# cell reports the idle metrics leaves one empty annotation a step, named
# WALL_STAMP + the wall clock (ns) read just before it: name minus start is
# the session's start.
WALL_STAMP = "bench.wall_ns."
MAX_OVERHANG_NS = 100_000       # a root span may stick out of its step's
#                                 bench.dispatch annotation by this much


def _roots(ctx, *names):
    """The root spans of these names that lie in the window (host clock)."""
    return [s for s in ctx.spans
            if s["parent"] is None and s["name"] in names
            and s["t0"] >= ctx.t_start and s["t1"] <= ctx.t_end]


def fwd_bwd_host_ms(ctx):
    """Per step (one ``gluon.update`` is one step): the host's time inside
    ``gluon.forward`` (the net's and the loss's) and ``autograd.backward``."""
    steps = len(_roots(ctx, UPDATE))
    if not steps:
        return None
    inside = _roots(ctx, FORWARD, BACKWARD)
    return sum(s["t1"] - s["t0"] for s in inside) / steps * 1e3


def update_host_ms(ctx):
    """Mean duration of ``gluon.update``."""
    updates = _roots(ctx, UPDATE)
    if not updates:
        return None
    return sum(s["t1"] - s["t0"] for s in updates) / len(updates) * 1e3


def update_programs(ctx):
    """Mean of the jitted calls an update dispatched (its ``programs``)."""
    counts = [s["args"]["programs"] for s in _roots(ctx, UPDATE)
              if "programs" in s["args"]]
    if not counts:
        return None
    return sum(counts) / len(counts)


def clock_overhang_ns(roots, dispatches):
    """How far the root spans of the traced steps stick out of the
    ``bench.dispatch`` annotation each was opened in, at most (ns); ``None``
    when no span falls among the annotations at all.  ``roots`` and
    ``dispatches`` are ``(start, end)`` on the trace's clock.  A span belongs
    to the annotation it overlaps most; one that overlaps none sticks out by
    its distance to the nearest."""
    if not dispatches:
        return None
    lo, hi = dispatches[0][0], dispatches[-1][1]
    worst = None
    for t0, t1 in roots:
        if not lo <= (t0 + t1) // 2 <= hi:
            continue            # a step before or after the traced ones
        start, end = max(dispatches,
                         key=lambda d: min(t1, d[1]) - max(t0, d[0]))
        out = max(start - t0, t1 - end, 0)
        worst = out if worst is None else max(worst, out)
    return worst


def session_starts(host):
    """The start of the profiler's session on the wall clock (ns), once for
    every ``WALL_STAMP`` annotation among the trace's host events, sorted:
    the wall clock its name carries minus its start on the trace's clock."""
    return sorted(int(name[len(WALL_STAMP):]) - t for name, t, _ in host
                  if name.startswith(WALL_STAMP))


def _idle_by_span(ctx):
    """``{span name: idle ns}`` of the first chip's traced window, with
    ``"uncovered"`` for the idle time no root span covers and ``"window"``
    for the window itself; ``None`` without a trace, without ns stamps or
    with a clock that does not fit.  Computed once a run, and noted."""
    if "_idle_by_span" in ctx.__dict__:
        return ctx._idle_by_span
    ctx._idle_by_span = table = _compute_idle_by_span(ctx)
    if table is not None:
        window = table["window"]
        ctx.note("idle_by_span", {
            "window_ms": window / 1e6,
            "idle_pct_of_window": {
                name: 100.0 * ns / window for name, ns in
                sorted(table.items(), key=lambda kv: -kv[1])
                if name != "window"},
            "inside_other_spans": ["jit.compile"]})
    return table


def _compute_idle_by_span(ctx):
    if ctx.trace is None:
        return None
    d = ctx.device_ids[0]
    w = ctx.trace.window(d)
    stamped = [s for s in ctx.spans if s.get("t0_ns") is not None
               and s.get("t1_ns") is not None]
    if w is None or not stamped:
        return None
    starts = session_starts(ctx.trace.host)
    if not starts:
        return None
    start = starts[len(starts) // 2]
    # from here on a span is (start, end) on the trace's clock
    placed = [(s, s["t0_ns"] - start, s["t1_ns"] - start) for s in stamped]
    dispatches = sorted((t, t + dur) for name, t, dur in ctx.trace.host
                        if name == "bench.dispatch")
    overhang = clock_overhang_ns(
        [(t0, t1) for s, t0, t1 in placed if s["parent"] is None],
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
    table, covered = {"window": hi - lo}, []
    # jit.compile is a child: its idle time lies inside its parent's
    for s, t0, t1 in placed:
        if s["parent"] is not None and s["name"] != "jit.compile":
            continue
        part = total(clip(gaps, t0, t1))
        if part:
            table[s["name"]] = table.get(s["name"], 0) + part
        if s["parent"] is None:
            covered.append((t0, t1))
    table["uncovered"] = total(subtract(gaps, union(covered)))
    return table


def _idle_pct(ctx, *names):
    table = _idle_by_span(ctx)
    if table is None:
        return None
    return 100.0 * sum(table.get(n, 0) for n in names) / table["window"]


def idle_in_fwd_bwd_pct(ctx):
    """Share of the traced window in which the core ran nothing while the
    host was inside ``gluon.forward`` or ``autograd.backward``."""
    return _idle_pct(ctx, FORWARD, BACKWARD)


def idle_in_update_pct(ctx):
    """The same inside ``gluon.update``."""
    return _idle_pct(ctx, UPDATE)
