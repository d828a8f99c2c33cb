"""Pipeline parallelism over a 'pp' mesh axis — TPU-native GPipe.

Reference capability (SURVEY.md §2.5 "model parallel" row): upstream MXNet
placed layer groups on devices with ``group2ctx`` and moved activations with
explicit copies. The TPU design instead runs ALL stages as one SPMD program:
stage parameters are stacked on a leading axis sharded over 'pp', and one
``lax.scan`` over pipeline ticks moves activations between neighbouring
stages with ``lax.ppermute`` (the activation hop rides ICI, compiled into
the step). Differentiable end-to-end — ``jax.grad`` through the scan gives
the 1F1B-equivalent backward for free, so a pipelined training step is just
``value_and_grad(pipeline_apply)`` under ``jit``.

The schedule is GPipe: with S stages and M microbatches the bubble fraction
is (S-1)/(M+S-1); choose M >= 4*S for <20% bubble (How to Scale Your Model,
pipelining chapter).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P
from jax import shard_map

from ..base import MXNetError
from .mesh import AXIS_DP, AXIS_PP

__all__ = ["pipeline_apply", "pipeline_local", "stack_stage_params",
           "Pipeline", "one_f_one_b_schedule", "bubble_fraction",
           "split_into_stages", "PipelineStageExecutor", "Schedule1F1B"]


def stack_stage_params(per_stage_params):
    """Stack a list of per-stage parameter pytrees (identical structure)
    into one pytree with a leading [n_stages] axis — shard it over 'pp'."""
    return jax.tree.map(lambda *xs: jnp.stack(xs), *per_stage_params)


def pipeline_local(stage_fn, params_local, micro_all, *, axis, n_stages,
                   n_microbatches):
    """GPipe tick schedule for use INSIDE an existing shard_map whose mesh
    binds ``axis`` — the composable core shared by ``pipeline_apply`` and
    multi-axis SPMD programs that pipeline alongside dp/tp/sp (mirrors
    ``ring_attention_local``).

    ``params_local``: this stage's (already-squeezed) parameter pytree.
    ``micro_all``: (n_microbatches, mb, ...) — replicated over ``axis``;
    stage 0 ingests from it. Returns the finished (n_microbatches, mb, ...)
    outputs, broadcast to every stage.
    """
    stage = lax.axis_index(axis)
    mb_shape = micro_all.shape[1:]
    n_ticks = n_microbatches + n_stages - 1
    # initial carries must already be device-varying over the pipeline axis
    # so the scan carry type stays fixed (shard_map vma typing); under
    # check_vma=False pcast is unavailable and also unnecessary
    state = _pcast_varying(jnp.zeros(mb_shape, micro_all.dtype), axis)
    outputs = _pcast_varying(jnp.zeros_like(micro_all), axis)

    def tick(carry, t):
        state, outputs = carry
        # stage 0 ingests microbatch t (if still in range); other
        # stages consume what arrived from the left neighbour
        feed_idx = jnp.clip(t, 0, n_microbatches - 1)
        inp = jnp.where(stage == 0, micro_all[feed_idx], state)
        out = stage_fn(params_local, inp)
        # the last stage writes its finished microbatch (t - S + 1)
        out_idx = jnp.clip(t - (n_stages - 1), 0, n_microbatches - 1)
        write = (stage == n_stages - 1) & (t >= n_stages - 1)
        outputs = jnp.where(
            write,
            outputs.at[out_idx].set(out),
            outputs)
        # shift activations one stage to the right (ring permute; the
        # wrap-around value into stage 0 is ignored — it re-reads
        # micro_all)
        state = lax.ppermute(
            out, axis,
            [(i, (i + 1) % n_stages) for i in range(n_stages)])
        return (state, outputs), None

    (state, outputs), _ = lax.scan(
        tick, (state, outputs), jnp.arange(n_ticks))
    # every device carries a full `outputs` buffer but only the last
    # stage's is real; broadcast it (psum of masked buffer)
    return lax.psum(
        jnp.where(stage == n_stages - 1, outputs,
                  jnp.zeros_like(outputs)), axis)


def _pcast_varying(x, axis):
    try:
        return lax.pcast(x, axis, to="varying")
    except Exception:  # noqa: BLE001 — check_vma=False context: no-op
        return x


def pipeline_apply(stage_fn, stacked_params, x, *, mesh, n_microbatches,
                   axis="pp"):
    """Run ``x`` through S pipeline stages on the mesh's ``axis``.

    stage_fn(params_one_stage, microbatch) -> microbatch' — the same
    callable for every stage (homogeneous pipelining, the transformer
    case). ``stacked_params`` has a leading [S] axis; ``x`` has a leading
    batch axis that is split into ``n_microbatches``.

    Returns the output batch (same leading shape as x). Differentiable.
    """
    n_stages = mesh.shape[axis]
    batch = x.shape[0]
    if batch % n_microbatches:
        raise MXNetError(
            f"batch {batch} not divisible by n_microbatches "
            f"{n_microbatches}")
    mb = batch // n_microbatches
    micro = x.reshape((n_microbatches, mb) + x.shape[1:])

    def spmd(params_s, micro_all):
        # params_s: this stage's params (leading axis sliced to 1) — squeeze
        params_s = jax.tree.map(lambda a: a[0], params_s)
        return pipeline_local(stage_fn, params_s, micro_all, axis=axis,
                              n_stages=n_stages,
                              n_microbatches=n_microbatches)

    param_spec = jax.tree.map(lambda _: P(axis), stacked_params)
    fn = shard_map(
        spmd, mesh=mesh,
        in_specs=(param_spec, P()),
        out_specs=P())
    out = fn(stacked_params, micro)
    return out.reshape((batch,) + out.shape[2:])


class Pipeline:
    """Convenience wrapper: hold stacked params + jit the pipelined forward.

    Example::

        def stage(params, x):
            return jnp.tanh(x @ params["w"] + params["b"])
        pp = Pipeline(stage, [stage0_params, ..., stage3_params],
                      mesh=make_mesh({"pp": 4}), n_microbatches=8)
        y = pp(x)
    """

    def __init__(self, stage_fn, per_stage_params, mesh, n_microbatches,
                 axis="pp"):
        self.stage_fn = stage_fn
        self.mesh = mesh
        self.axis = axis
        self.n_microbatches = n_microbatches
        stacked = stack_stage_params(per_stage_params)
        self.params = jax.tree.map(
            lambda a: jax.device_put(a, NamedSharding(
                mesh, P(*([axis] + [None] * (a.ndim - 1))))), stacked)
        self._jitted = jax.jit(functools.partial(
            pipeline_apply, stage_fn, mesh=mesh,
            n_microbatches=n_microbatches, axis=axis))

    def __call__(self, x):
        return self._jitted(self.params, x)


# ---------------------------------------------------------------------------
# 1F1B (PipeDream-flush) schedule + the host-driven stage executor
# (ISSUE 11 tentpole).  The GPipe scan above runs every stage as one SPMD
# program — ideal when stages are homogeneous.  The executor below is the
# trainer-facing half: it pipelines an ARBITRARY (Hybrid)Sequential gluon
# model over per-stage device submeshes (MeshConfig.stage_mesh), running
# the canonical one-forward-one-backward schedule from the host with one
# AOT-jitted forward / recompute-backward / update program per stage.
# Stage parameters and optimizer state exist ONLY on their stage's
# devices (pipeline-staged params, 1/S memory); dp (and tp, via the
# sharding algebra on each stage submesh) compose inside every stage
# program.  When each stage's gradients become FINAL (its last backward
# microbatch), the executor fires the PR 5 grad-ready hooks — so an
# installed OverlapScheduler launches its bucketed dp collectives right
# there, inside the pipeline bubble, while earlier stages are still in
# backward — and dispatches that stage's optimizer update into the same
# bubble.
# ---------------------------------------------------------------------------

def bubble_fraction(n_stages, n_microbatches):
    """Analytic 1F1B bubble fraction: (S-1)/(M+S-1) of the schedule is
    idle per stage (same as GPipe; 1F1B wins on activation memory, not
    bubble).  Choose M >= 4*S for <20%."""
    s, m = int(n_stages), int(n_microbatches)
    if s < 1 or m < 1:
        raise MXNetError("bubble_fraction: need n_stages, n_microbatches"
                         " >= 1")
    return (s - 1) / (m + s - 1)


class Schedule1F1B:
    """The materialized tick table of a 1F1B schedule.

    ``ops_by_stage[s]`` — ``[('F'|'B', microbatch), ...]`` in execution
    order (no idles).  ``ticks`` — per tick, ``{stage: (phase, mb)}``
    for the stages that act.  ``order`` — the flat host dispatch order
    (tick-major; ops within a tick are dependency-free).
    ``bubble_ticks(s)`` — idle ticks of stage ``s`` inside the active
    window.
    """

    def __init__(self, n_stages, n_microbatches, ops_by_stage, ticks):
        self.n_stages = n_stages
        self.n_microbatches = n_microbatches
        self.ops_by_stage = ops_by_stage
        self.ticks = ticks
        self.order = [(s, phase, mb)
                      for tick in ticks
                      for s, (phase, mb) in sorted(tick.items())]

    @property
    def n_ticks(self):
        return len(self.ticks)

    def bubble_ticks(self, stage):
        active = [t for t, ops in enumerate(self.ticks) if stage in ops]
        return (active[-1] - active[0] + 1) - len(active)

    @property
    def bubble_frac(self):
        return bubble_fraction(self.n_stages, self.n_microbatches)


def one_f_one_b_schedule(n_stages, n_microbatches):
    """Build the canonical non-interleaved 1F1B schedule (PipeDream-
    flush / Megatron): stage ``s`` runs ``min(M, S-1-s)`` warmup
    forwards, then strictly alternates F,B (one forward, one backward)
    until its M forwards are done, then drains the remaining backwards.
    Dependencies: F(s,m) needs F(s-1,m); B(s,m) needs B(s+1,m) and
    F(s,m).  A stage whose scheduled op is not yet data-ready idles —
    those are the bubbles the executor fills with grad communication
    and optimizer updates."""
    s_n, m_n = int(n_stages), int(n_microbatches)
    if s_n < 1 or m_n < 1:
        raise MXNetError("one_f_one_b_schedule: need n_stages, "
                         "n_microbatches >= 1")
    warmup = [min(m_n, s_n - 1 - s) for s in range(s_n)]
    f_done = [0] * s_n
    b_done = [0] * s_n
    f_tick = [[None] * m_n for _ in range(s_n)]
    b_tick = [[None] * m_n for _ in range(s_n)]
    # strict F/B alternation state once warmup is over ('F' first)
    next_phase = ["F"] * s_n
    ops_by_stage = [[] for _ in range(s_n)]
    ticks = []
    total = 2 * s_n * m_n
    done = 0
    t = 0
    while done < total:
        if t > 2 * total + 2 * s_n:   # defensive: schedule must converge
            raise MXNetError("1F1B schedule failed to converge")
        this = {}
        for s in range(s_n):
            can_f = (f_done[s] < m_n and
                     (s == 0 or (f_tick[s - 1][f_done[s]] is not None and
                                 f_tick[s - 1][f_done[s]] < t)))
            can_b = (b_done[s] < f_done[s] and
                     (s == s_n - 1 or
                      (b_tick[s + 1][b_done[s]] is not None and
                       b_tick[s + 1][b_done[s]] < t)))
            if f_done[s] < warmup[s]:
                want = "F"                       # warmup: forwards only
            elif f_done[s] >= m_n:
                want = "B"                       # cooldown: drain
            else:
                want = next_phase[s]             # steady 1F1B
            if want == "F" and can_f:
                this[s] = ("F", f_done[s])
            elif want == "B" and can_b:
                this[s] = ("B", b_done[s])
            # else: bubble tick for this stage
        for s, (phase, mb) in this.items():
            if phase == "F":
                f_tick[s][mb] = t
                f_done[s] += 1
                if f_done[s] > warmup[s]:
                    next_phase[s] = "B"
            else:
                b_tick[s][mb] = t
                b_done[s] += 1
                next_phase[s] = "F"
            ops_by_stage[s].append((phase, mb))
            done += 1
        ticks.append(this)
        t += 1
    return Schedule1F1B(s_n, m_n, ops_by_stage, ticks)


def split_into_stages(block, n_stages):
    """Partition a ``(Hybrid)Sequential`` gluon block into ``n_stages``
    contiguous child groups, balanced by parameter element count.
    Returns a list of child-block lists.  Only sequential containers
    qualify: their forward IS the composition of their children, which
    is the contract the stage executor relies on (an arbitrary block's
    forward cannot be split from the outside)."""
    from ..gluon import nn as _nn
    if not isinstance(block, (_nn.Sequential, _nn.HybridSequential)):
        raise MXNetError(
            f"pipeline parallelism needs a Sequential/HybridSequential "
            f"model (the forward must be the composition of its "
            f"children); got {type(block).__name__}.  Wrap the stage-"
            f"able body in nn.HybridSequential or set pp=1")
    children = list(block._children.values())
    if len(children) < n_stages:
        raise MXNetError(
            f"cannot split {len(children)} layers into {n_stages} "
            f"pipeline stages")
    weights = []
    for c in children:
        n = 0
        for p in c.collect_params().values():
            if p.shape:
                k = 1
                for d in p.shape:
                    k *= int(d)
                n += k
        weights.append(max(n, 1))
    total = sum(weights)
    stages, cur, acc = [], [], 0
    remaining = list(range(len(children)))
    for i, c in enumerate(children):
        cur.append(c)
        acc += weights[i]
        left = len(children) - i - 1
        need = n_stages - len(stages) - 1
        # close the stage when it reached its fair share — unless the
        # remaining children are exactly enough to fill remaining stages
        if len(stages) < n_stages - 1 and \
                (acc >= total / n_stages or left == need):
            stages.append(cur)
            cur, acc = [], 0
    stages.append(cur)
    assert len(stages) == n_stages and all(stages)
    return stages


class PipelineStageExecutor:
    """Host-driven 1F1B over per-stage submeshes (the trainer's pp
    engine; see module comment above).

    ``stage_children[s]`` — the gluon child blocks of stage ``s`` (from
    :func:`split_into_stages`).  ``config`` — the 3D
    :class:`~mxnet_tpu.parallel.mesh.MeshConfig`; stage ``s`` computes
    on ``config.stage_mesh(s, devices)``.  ``rule_apply(p, g, s, lr)``
    and ``rule_init(p)`` — the trainer's fused optimizer kernels (ONE
    update source with every other path).  Backward is stage-level
    rematerialization: the backward program re-runs the stage forward
    inside ``jax.vjp`` — only stage-boundary activations are stashed
    between phases, the 1F1B memory shape.

    Events land in :attr:`events` per step:
    ``('F'|'B', stage, mb)``, ``('ready', stage)`` (grads final, PR 5
    grad-ready hooks fired — an installed OverlapScheduler launches its
    bucketed collectives HERE, in the bubble), ``('update', stage)``.
    """

    def __init__(self, stage_children, loss_fn, config, devices,
                 rule_init, rule_apply, n_microbatches):
        if config.pp != len(stage_children):
            raise MXNetError(
                f"executor got {len(stage_children)} stages for "
                f"pp={config.pp}")
        self.cfg = config
        self.loss_fn = loss_fn
        self._devices = list(devices)
        self._rule_init = rule_init
        self._rule_apply = rule_apply
        self.n_microbatches = int(n_microbatches)
        if self.n_microbatches < 1:
            raise MXNetError("pp: n_microbatches must be >= 1")
        self.stage_children = stage_children
        # per-stage sorted param objects (sorted by name, the trainer
        # convention — state_dict round-trips through the same order)
        self.stage_params = []
        for chs in stage_children:
            items = []
            for c in chs:
                items.extend(sorted(c.collect_params().items()))
            self.stage_params.append([p for _, p in sorted(items)])
        self.stage_meshes = [config.stage_mesh(s, self._devices)
                             for s in range(config.pp)]
        self._param_vals = None      # [stage][i] device arrays
        self._opt_state = None       # [stage][i] state trees
        self._fwd = {}
        self._bwd = {}
        self._upd = {}
        self.events = []
        self.last_schedule = None

    # -- placement -------------------------------------------------------
    def _param_sharding(self, stage, p):
        mesh = self.stage_meshes[stage]
        if p.shard_spec is not None:
            return NamedSharding(mesh, p.shard_spec)
        return NamedSharding(mesh, P())

    def _batch_sharding(self, stage, ndim):
        mesh = self.stage_meshes[stage]
        spec = [None] * ndim
        if ndim:
            spec[0] = AXIS_DP if AXIS_DP in mesh.axis_names else None
        return NamedSharding(mesh, P(*spec))

    def ensure_ready(self):
        if self._param_vals is None:
            self._param_vals = [
                [jax.device_put(p.data().data,
                                self._param_sharding(s, p))
                 for p in params]
                for s, params in enumerate(self.stage_params)]
        else:
            for s, params in enumerate(self.stage_params):
                for i, p in enumerate(params):
                    if p._data is not None and \
                            p._data._data is not self._param_vals[s][i]:
                        self._param_vals[s][i] = jax.device_put(
                            p.data().data, self._param_sharding(s, p))
        if self._opt_state is None:
            self._opt_state = [
                [jax.tree.map(
                    lambda x: jax.device_put(
                        x, NamedSharding(self.stage_meshes[s], P())),
                    self._rule_init(v)) for v in vals]
                for s, vals in enumerate(self._param_vals)]

    # -- per-stage programs ---------------------------------------------
    def _stage_apply(self, s):
        """(pv, key, x) -> y: the traced forward of stage ``s`` — same
        bind/trace discipline as the trainer's loss closure."""
        from .. import _tape
        from ..ndarray.ndarray import NDArray
        from ..ndarray import random as _rnd
        from ..gluon.parameter import _bind_params
        children = self.stage_children[s]
        params = self.stage_params[s]

        def apply(pv, key, x):
            prev = _tape.set_training(True)
            binding = {p: NDArray(v) for p, v in zip(params, pv)}
            try:
                with _tape.trace_scope(), _bind_params(binding), \
                        _rnd.trace_key_scope(key):
                    out = NDArray(x)
                    for c in children:
                        out = c.forward(out)
            finally:
                _tape.set_training(prev)
            return out.data
        return apply

    def _programs(self, s):
        if s in self._fwd:
            return
        apply = self._stage_apply(s)
        last = s == self.cfg.pp - 1
        loss_fn = self.loss_fn

        def fwd(pv, key, x):
            return apply(list(pv), key, x)

        if last:
            from ..ndarray.ndarray import NDArray

            def loss_of(pv, x, key, label):
                y = apply(list(pv), key, x)
                return jnp.mean(loss_fn(NDArray(y), NDArray(label)).data)

            def bwd(pv, key, x, label):
                val, (gp, gx) = jax.value_and_grad(
                    loss_of, argnums=(0, 1))(list(pv), x, key, label)
                return val, gp, gx
        else:
            def bwd(pv, key, x, gy):
                _, pull = jax.vjp(
                    lambda pv_, x_: apply(list(pv_), key, x_),
                    list(pv), x)
                gp, gx = pull(gy)
                return gp, gx

        rule_apply = self._rule_apply

        def upd(pv, grads, st, lr):
            new_p, new_s = [], []
            for p_, g_, s_ in zip(pv, grads, st):
                np_, ns_ = rule_apply(p_, g_.astype(p_.dtype), s_, lr)
                new_p.append(np_)
                new_s.append(ns_)
            return new_p, new_s

        self._fwd[s] = jax.jit(fwd)
        self._bwd[s] = jax.jit(bwd)
        self._upd[s] = jax.jit(upd)

    # -- the 1F1B step ---------------------------------------------------
    def step(self, x, label, key, lr, n_micro=1):
        """One optimizer step: ``M = n_microbatches * n_micro``
        microbatches through the 1F1B schedule, grads meaned over all
        of them, one update per stage dispatched into that stage's
        bubble.  Returns the scalar mean loss (a jax array)."""
        from .. import _tape
        from .. import telemetry as _telem
        S = self.cfg.pp
        M = self.n_microbatches * max(1, int(n_micro))
        b = x.shape[0]
        if b % M:
            raise MXNetError(
                f"pp: batch {b} not divisible by {M} microbatches "
                f"(pp_microbatches={self.n_microbatches} x n_micro="
                f"{n_micro})")
        mb = b // M
        if self.cfg.dp > 1 and mb % self.cfg.dp:
            raise MXNetError(
                f"pp: microbatch {mb} not divisible by dp={self.cfg.dp}")
        self.ensure_ready()
        for s in range(S):
            self._programs(s)
        sched = one_f_one_b_schedule(S, M)
        self.last_schedule = sched
        micro_x = [jax.device_put(
            x[i * mb:(i + 1) * mb], self._batch_sharding(0, x.ndim))
            for i in range(M)]
        micro_lab = [jax.device_put(
            label[i * mb:(i + 1) * mb],
            self._batch_sharding(S - 1, label.ndim)) for i in range(M)]
        keys = {(s, i): jax.random.fold_in(key, s * 100003 + i)
                for s in range(S) for i in range(M)}
        stash = [[None] * M for _ in range(S)]    # stage input per mb
        acts = [[None] * M for _ in range(S)]     # stage output per mb
        gys = [[None] * M for _ in range(S)]      # cotangent from right
        gacc = [None] * S
        losses = []
        b_count = [0] * S
        self.events = events = []
        for s, phase, i in sched.order:
            if phase == "F":
                if s == 0:
                    xin = micro_x[i]
                else:
                    xin = jax.device_put(
                        acts[s - 1][i],
                        self._batch_sharding(s, acts[s - 1][i].ndim))
                stash[s][i] = xin
                acts[s][i] = self._fwd[s](self._param_vals[s],
                                          keys[(s, i)], xin)
                events.append(("F", s, i))
                continue
            # backward (stage-level remat: re-runs the stage forward)
            if s == S - 1:
                val, gp, gx = self._bwd[s](self._param_vals[s],
                                           keys[(s, i)], stash[s][i],
                                           micro_lab[i])
                losses.append(val)
            else:
                gy = jax.device_put(
                    gys[s][i], self._batch_sharding(s, gys[s][i].ndim))
                gp, gx = self._bwd[s](self._param_vals[s],
                                      keys[(s, i)], stash[s][i], gy)
            if s > 0:
                gys[s - 1][i] = gx
            stash[s][i] = None                     # 1F1B memory shape
            acts[s][i] = None
            if gacc[s] is None:
                gacc[s] = list(gp)
            else:
                gacc[s] = [a + g for a, g in zip(gacc[s], gp)]
            b_count[s] += 1
            events.append(("B", s, i))
            if b_count[s] == M:
                self._finish_stage(s, gacc[s], M, lr, events, _tape,
                                   _telem)
                gacc[s] = None
        loss = jnp.mean(jnp.stack(losses)) if losses else jnp.zeros(())
        # write updated params back into the block (NDArray views on the
        # stage submeshes — checkpoint/parity readers gather on demand)
        for s, params in enumerate(self.stage_params):
            for p, v in zip(params, self._param_vals[s]):
                p._data._set_data(v)
        return loss

    def _finish_stage(self, s, gsum, M, lr, events, _tape, _telem):
        """Stage ``s``'s gradients just became FINAL (its last backward
        microbatch) while earlier stages are still in backward — the
        1F1B bubble.  Everything that only needs THIS stage's grads
        launches now: grad-ready hooks (an installed OverlapScheduler
        dispatches its bucketed dp collectives from them), then the
        stage's optimizer update.  All dispatches are async; nothing
        here blocks on the device."""
        grads = [g / M for g in gsum]
        for p, g in zip(self.stage_params[s], grads):
            if p._data is not None:
                _tape._finalize_leaf(p._data, g)    # fires PR 5 hooks
        events.append(("ready", s))
        if _telem.enabled():
            _telem.event("pp.stage_grads_ready", stage=s)
        new_p, new_s = self._upd[s](self._param_vals[s], grads,
                                    self._opt_state[s],
                                    jnp.asarray(lr, jnp.float32))
        self._param_vals[s] = list(new_p)
        self._opt_state[s] = list(new_s)
        events.append(("update", s))

    # -- state (per-parameter space; the trainer merges stages) ----------
    def iter_params(self):
        """Yield (stage, local_index, param, value, state)."""
        self.ensure_ready()
        for s, params in enumerate(self.stage_params):
            for i, p in enumerate(params):
                yield s, i, p, self._param_vals[s][i], \
                    self._opt_state[s][i]

    def set_state(self, stage, i, state_tree):
        mesh = self.stage_meshes[stage]
        self._opt_state[stage][i] = jax.tree.map(
            lambda x: jax.device_put(jnp.asarray(x),
                                     NamedSharding(mesh, P())),
            state_tree)

    def state_bytes(self):
        total = 0
        if self._opt_state is not None:
            for leaf in jax.tree.leaves(self._opt_state):
                total += leaf.size * leaf.dtype.itemsize
        return total
