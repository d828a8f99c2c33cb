"""Fused data-parallel training: one jitted step, grads reduced in-graph.

This is the TPU replacement for the reference's hot loop
(SURVEY.md §3.2 TPU mapping): `record -> forward -> backward ->
kvstore.push/pull -> optimizer.update` becomes ONE jit(train_step) with
donated params/optimizer state. The batch is sharded over the mesh 'dp'
axis. Two gradient-sync pipelines exist:

- default: parameters replicated, XLA inserts the gradient all-reduce
  over ICI automatically from the sharding algebra (SURVEY.md §2.6).
- ``shard_updates=True`` (ZeRO-1, ISSUE 3 tentpole): the step runs as a
  ``shard_map`` over 'dp' — per-chip fwd/bwd, gradients flattened into
  size-bounded buckets (``MXTPU_COMM_BUCKET_MB``), an explicit
  reduce-scatter (optionally quantized on the wire via
  ``MXTPU_COMM_DTYPE=bf16|int8``), a 1/N-sized optimizer update against
  bucket-sharded optimizer state, and one all-gather of the fresh
  parameters per bucket.  Same ring wire bytes as all-reduce
  (RS+AG == AR), 1/N optimizer HBM and update compute per chip, and
  few/large collectives instead of one per tensor (parallel/zero.py;
  arXiv:1909.09756 weight-update sharding, arXiv:2506.17615 EQuARX).
  ``MXTPU_SHARDED_SYNC=0`` is the kill switch back to the psum path.
"""
from __future__ import annotations

import functools
import math

import numpy as _np
import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from ..base import MXNetError
from ..lint import donation as _donation
from ..ndarray.ndarray import NDArray
from ..ndarray import random as _rnd
from .. import _tape
from .. import telemetry as _telem
from ..telemetry import tracing as _trace
from ..telemetry import watchdog as _watchdog
from ..gluon.parameter import _bind_params
from jax import shard_map
from .mesh import (current_mesh, make_mesh, mesh_scope, MeshConfig,
                   AXIS_DP, AXIS_TP, AXIS_PP)
from . import zero as _zero

__all__ = ["DataParallelTrainer", "all_reduce_gradients"]


# The update math lives in ONE place — mx.optimizer's functional kernels
# (optimizer.fused_rule); the eager Optimizer.update path delegates to the
# same kernels, so fused and eager training can never diverge (VERDICT r1
# #6: the old local copies silently mapped NAG->SGD and AdamW->Adam).
from ..optimizer.optimizer import fused_rule, _FUSED_KERNELS

_RULES = _FUSED_KERNELS  # names the fused path accepts


def _step_span(step):
    """A step entry point under its scoped ``train.step`` root span
    (ambient, and a ``TraceAnnotation`` in any profile that runs); the
    body commits the phases as its children."""
    @functools.wraps(step)
    def traced(self, *args, **kwargs):
        with _trace.span("train.step"):
            return step(self, *args, **kwargs)
    return traced


class DataParallelTrainer:
    """jit(train_step) over a mesh; drop-in upgrade from gluon.Trainer.

    Usage::

        mesh = parallel.make_mesh({'dp': -1})
        trainer = parallel.DataParallelTrainer(net, loss_fn, 'sgd',
            {'learning_rate': 0.1, 'momentum': 0.9}, mesh=mesh)
        loss = trainer.step(data, label)          # one fused jitted step

    The forward/backward/reduce/update all execute as a single XLA program
    with donated buffers (static_alloc/static_shape analog).
    """

    def __init__(self, block, loss_fn, optimizer="sgd", optimizer_params=None,
                 mesh=None, batch_axis=0, dtype=None, donate=True,
                 shard_updates=False, label_batch_axis=None,
                 mesh_config=None, pp_microbatches=None):
        self.block = block
        self.loss_fn = loss_fn
        # ONE source of mesh truth (ISSUE 11): an explicit MeshConfig
        # wins, then an explicit/ambient Mesh (config derived from its
        # axis names), then the MXTPU_MESH env spec, then flat dp over
        # all devices — the unset-env default builds exactly the
        # Mesh(('dp',), N) of the flat trainer, bitwise.
        if mesh_config is None and mesh is None:
            mesh = current_mesh()
        if mesh is not None:
            self.mesh = mesh
            self.mesh_config = MeshConfig.for_mesh(mesh)
        else:
            cfg = mesh_config or MeshConfig.from_env() \
                or MeshConfig(dp=-1)
            self.mesh_config = cfg = cfg.resolve(len(jax.devices()))
            self.mesh = cfg.build()
        # pipeline microbatch knob: arg > MXTPU_PP_MICROBATCH env >
        # 2 ticks of work per stage (the smallest schedule with a
        # steady-state 1F1B phase)
        if pp_microbatches is None:
            import os as _os
            pp_microbatches = int(_os.environ.get(
                "MXTPU_PP_MICROBATCH", 2 * self.mesh_config.pp))
        self._pp_microbatches = max(1, int(pp_microbatches))
        self._pp_exec = None          # built on first pp step
        self.batch_axis = batch_axis
        self._label_bax = (batch_axis if label_batch_axis is None
                           else label_batch_axis)
        # ZeRO-1 sharded gradient sync (see module docstring). Resolved
        # lazily in _zero1_active(): needs the optimizer rule (elementwise
        # kernels only) and the parameter shard specs (pure-dp only).
        # The raw request survives separately so rebuild() can re-derive
        # the effective flag for a different world size (dp may cross 1).
        self._shard_requested = bool(shard_updates)
        # ZeRO-1 runs on the pure-dp composition only: tp-sharded
        # params and pp-staged params have their own state layouts
        self._shard_updates = self._shard_requested and \
            self.mesh.shape.get(AXIS_DP, 1) > 1 and \
            self.mesh_config.tp == 1 and self.mesh_config.pp == 1
        self._zero1 = None              # tri-state; resolved lazily
        self._plan = None               # zero.BucketPlan once params known
        self._comm_dtype = _zero.comm_dtype()   # read ONCE at construction
        # backward-overlapped comm (ISSUE 5): read ONCE, like the wire
        # dtype — a mid-training env flip must not re-plan the buckets
        self._overlap_comm = _zero.overlap_comm_enabled()
        params_kwargs = dict(optimizer_params or {})
        self._lr = params_kwargs.pop("learning_rate", 0.01)
        self._lr_scheduler = params_kwargs.pop("lr_scheduler", None)
        wd = params_kwargs.pop("wd", 0.0)
        clip = params_kwargs.pop("clip_gradient", None)
        name = optimizer.lower() if isinstance(optimizer, str) else "sgd"
        if name not in _RULES:
            raise MXNetError(
                f"DataParallelTrainer supports {sorted(_RULES)}; for "
                f"'{optimizer}' use gluon.Trainer (eager path)")
        self._rule_name = name
        self._rule_init, _kernel_apply = fused_rule(
            name, clip_gradient=clip, **params_kwargs)
        self._rule_apply = lambda p, g, s, lr: _kernel_apply(p, g, s, lr, wd)
        # ZeRO-1 flat-shard updates route through the fused bucket rule:
        # on TPU one Pallas kernel walks the whole flat bucket (ISSUE 6);
        # everywhere else it IS the fused_rule kernel (bitwise identical)
        from ..ops.fused_update import fused_bucket_rule
        _, _bucket_kernel = fused_bucket_rule(
            name, clip_gradient=clip, **params_kwargs)
        self._bucket_apply = lambda p, g, s, lr: \
            _bucket_kernel(p, g, s, lr, wd)
        self._param_objs = None
        self._param_vals = None   # device-resident, sharded; owned by us
        self._opt_state = None
        self._jitted = None
        self._jit_accum_cache = {}
        self._jit_multi_cache = {}
        self._jit_zero1_cache = {}
        self._num_update = 0
        self._donate = donate
        self._last_entry = None      # clock at the last step's entry
        # memory honesty (ISSUE 15): exact byte gauges for the flight
        # recorder's memory block, published once per build
        self._mem_gauges_stale = True

    # -- parameter plumbing --------------------------------------------
    def _collect(self, *args):
        if self._param_objs is None:
            if any(p._data is None
                   for p in self.block.collect_params().values()):
                # resolve deferred shapes with one eager forward
                with _tape.trace_scope():
                    self.block.forward(*args)
            items = sorted(self.block.collect_params().items())
            self._param_objs = [p for _, p in items]
        return self._param_objs

    def _param_sharding(self, p):
        if p.shard_spec is not None:
            return NamedSharding(self.mesh, p.shard_spec)
        return NamedSharding(self.mesh, P())

    def _eff_bax(self, ndim, is_label=False):
        """Effective batch axis for an array of the given rank.

        Inputs carry the batch on ``batch_axis``; the label carries it
        on ``label_batch_axis`` (defaults to batch_axis).  Rank-1 arrays
        are per-sample vectors whatever the nominal axis (classic (B,)
        labels under time-major batch_axis=1).  Rank>=2 arrays MUST have
        their batch on the configured axis — that is the API contract; a
        (B, C) soft-label under time-major data needs
        ``label_batch_axis=0``, it cannot be inferred from shape."""
        ax = self._label_bax if is_label else self.batch_axis
        if ndim <= 1:
            return 0
        if ax >= ndim:
            raise MXNetError(
                f"batch axis {ax} out of range for rank-{ndim} array")
        return ax

    def _batch_sharding(self, b, is_label=False):
        if not b.ndim:
            return NamedSharding(self.mesh, P())
        ax = self._eff_bax(b.ndim, is_label)
        spec = [None] * b.ndim
        spec[ax] = AXIS_DP
        return NamedSharding(self.mesh, P(*spec))

    def _batch_spec(self, ndim, is_label=False):
        """The PartitionSpec twin of :meth:`_batch_sharding` (shard_map
        in_specs need bare specs, not NamedShardings)."""
        if not ndim:
            return P()
        spec = [None] * ndim
        spec[self._eff_bax(ndim, is_label)] = AXIS_DP
        return P(*spec)

    def _put_batch(self, inputs):
        """device_put every batch array with its batch sharding; the
        LAST array is the label (single convention for step/step_accum)."""
        return [jax.device_put(b, self._batch_sharding(
            b, is_label=(i == len(inputs) - 1)))
            for i, b in enumerate(inputs)]

    def _stacked_spec(self, ndim, is_label=False):
        """PartitionSpec for a K-step stacked batch array (K, batch,
        ...): leading scan axis replicated, within-batch sharding by the
        same ``_eff_bax`` rule as :meth:`_batch_spec`."""
        inner = [None] * (ndim - 1)
        if ndim - 1 >= 1:
            inner[self._eff_bax(ndim - 1, is_label)] = AXIS_DP
        return P(*([None] + inner))

    def _put_stacked(self, steps):
        """Stack K per-step batches along a new leading axis and place
        them on the mesh (one H2D per input position, not one per
        step)."""
        n_in = len(steps[0])
        out = []
        for i in range(n_in):
            stacked = jnp.stack([s[i] for s in steps])
            sharding = NamedSharding(self.mesh, self._stacked_spec(
                stacked.ndim, is_label=(i == n_in - 1)))
            out.append(jax.device_put(stacked, sharding))
        return out

    def _make_loss_of(self, manual=False):
        """The traced fwd+loss closure — ONE source for every step
        variant (plain, accumulating, multi-step), replicated or sharded.

        While it traces, the ambient mesh says how the step is split:
        this trainer's mesh when XLA partitions the step itself (the
        psum path), none when the trace already runs per chip inside a
        shard_map (``manual``, the ZeRO-1 path).  A Pallas kernel reads
        it to wrap itself in the shard_map XLA cannot add for it."""
        block = self.block
        loss_fn = self.loss_fn
        params = self._param_objs
        ambient = None if manual else self.mesh

        def loss_of(pv, key, inputs, label):
            prev = _tape.set_training(True)
            binding = {p: NDArray(v) for p, v in zip(params, pv)}
            try:
                # train.loss names the forward's operations in a profile;
                # the backward's carry transpose(jvp(train.loss))
                with _tape.trace_scope(), _bind_params(binding), \
                        _rnd.trace_key_scope(key), mesh_scope(ambient), \
                        jax.named_scope("train.loss"):
                    out = block.forward(*[NDArray(b) for b in inputs])
                    loss = loss_fn(out, NDArray(label))
            finally:
                _tape.set_training(prev)
            return jnp.mean(loss.data)
        return loss_of

    def _apply_updates(self, param_vals, grads, opt_state, lr):
        """The replicated optimizer update — ONE source for every
        psum-path step variant (VERDICT r1 #6: duplicated update loops
        silently diverged once; never again).  The ZeRO-1 pipeline has
        its own single source, :meth:`_zero1_sync_update`."""
        rule_apply = self._rule_apply
        new_params, new_state = [], []
        with jax.named_scope("train.update"):
            for p, g, s in zip(param_vals, grads, opt_state):
                np_, ns = rule_apply(p, g.astype(p.dtype), s, lr)
                new_params.append(np_)
                new_state.append(ns)
        return new_params, new_state

    def _build(self):
        """The fused fwd/bwd/reduce/update step of :meth:`step`."""
        loss_of = self._make_loss_of()

        def train_step(param_vals, opt_state, lr, key, *batch):
            loss, grads = jax.value_and_grad(loss_of)(
                list(param_vals), key, list(batch[:-1]), batch[-1])
            new_params, new_state = self._apply_updates(
                param_vals, grads, opt_state, lr)
            return new_params, new_state, loss

        donate = (0, 1) if self._donate else ()
        self._jitted = jax.jit(train_step, donate_argnums=donate)

    def _grad_fn(self, loss_of, n_micro):
        """``(param_vals, key, inputs, label) -> (grads, mean_loss)`` —
        plain gradients or the ``n_micro``-microbatch accumulation scan
        (the step_accum skeleton).  ONE source for the psum, ZeRO-1 and
        multi-step step bodies (they can never diverge)."""
        if n_micro <= 1:
            def plain(param_vals, key, inputs, label):
                loss, grads = jax.value_and_grad(loss_of)(
                    list(param_vals), key, inputs, label)
                return grads, loss
            return plain
        split_micro = self._micro_splitter(n_micro)

        def accum(param_vals, key, inputs, label):
            micro_in = [split_micro(b) for b in inputs]
            micro_lab = split_micro(label, is_label=True)
            keys = jax.random.split(key, n_micro)

            def scan_step(carry, xs):
                acc, loss_sum = carry
                *mb, lab, k = xs
                loss, grads = jax.value_and_grad(loss_of)(
                    list(param_vals), k, mb, lab)
                acc = [a + g.astype(jnp.float32)
                       for a, g in zip(acc, grads)]
                return (acc, loss_sum + loss), None

            init = ([jnp.zeros(v.shape, jnp.float32)
                     for v in param_vals], jnp.zeros((), jnp.float32))
            (acc, loss_sum), _ = lax.scan(
                scan_step, init, tuple(micro_in) + (micro_lab, keys))
            return [g / n_micro for g in acc], loss_sum / n_micro
        return accum

    def _build_accum(self, n_micro):
        """Fused step with in-graph gradient accumulation: a ``lax.scan``
        over ``n_micro`` microbatches (one microbatch's activations live
        at a time), f32 grad accumulation, ONE optimizer update on the
        mean grad.  Big-batch training without big-batch activation
        memory — the reference reaches the same regime eagerly via
        grad_req='add' + stepping every N batches (gluon/trainer.py);
        here the whole accumulation compiles into the step.  Loss and
        update logic come from the same _grad_fn/_apply_updates the
        plain step uses (single source, cannot diverge)."""
        grad_fn = self._grad_fn(self._make_loss_of(), n_micro)

        def train_step(param_vals, opt_state, lr, key, *batch):
            inputs, label = list(batch[:-1]), batch[-1]
            mean_grads, mean_loss = grad_fn(param_vals, key, inputs,
                                            label)
            new_params, new_state = self._apply_updates(
                param_vals, mean_grads, opt_state, lr)
            return new_params, new_state, mean_loss

        donate = (0, 1) if self._donate else ()
        return jax.jit(train_step, donate_argnums=donate)

    def _build_multi(self, n_steps, n_micro):
        """K = ``n_steps`` training steps lowered into ONE XLA program
        (ISSUE 6 tentpole): a ``lax.scan`` over device-resident batches
        with ALL carry state — params, optimizer slots — donated, so the
        host dispatches once per K steps instead of once per step.
        Per-step lrs and PRNG keys arrive as stacked (K,) vectors drawn
        host-side from the SAME streams the per-step path uses, so K>1
        matches K=1 bitwise (the per-step math is _grad_fn +
        _apply_updates, the exact single-step bodies)."""
        grad_fn = self._grad_fn(self._make_loss_of(), n_micro)

        def train_multi(param_vals, opt_state, lrs, keys, *stacked):
            def one_step(carry, xs):
                pv, st = carry
                lr, key = xs[0], xs[1]
                batch = list(xs[2:])
                grads, loss = grad_fn(pv, key, batch[:-1], batch[-1])
                new_p, new_s = self._apply_updates(pv, grads, st, lr)
                return (new_p, new_s), loss

            (new_params, new_state), losses = lax.scan(
                one_step, (list(param_vals), opt_state),
                (lrs, keys) + tuple(stacked))
            return new_params, new_state, losses

        donate = (0, 1) if self._donate else ()
        return jax.jit(train_multi, donate_argnums=donate)

    def _micro_splitter(self, n_micro):
        def split_micro(b, is_label=False):
            # split each array's own effective BATCH axis into n_micro
            # leading scan slices, preserving the layout within each
            # microbatch (rank-1 labels under batch_axis=1 split on
            # axis 0 — see _eff_bax)
            bax = self._eff_bax(b.ndim, is_label)
            s = b.shape
            b = b.reshape(s[:bax] + (n_micro, s[bax] // n_micro)
                          + s[bax + 1:])
            return jnp.moveaxis(b, bax, 0)
        return split_micro

    @_step_span
    def step_accum(self, *batch, n_micro):
        """One fused update from ``n_micro`` microbatches: batch arrays
        carry n_micro * B elements on ``batch_axis`` and are consumed
        microbatch-at-a-time inside the compiled step (see
        :meth:`_build_accum`).  Returns the mean microbatch loss."""
        if n_micro < 1:
            raise MXNetError("step_accum: n_micro must be >= 1")
        if self._pp_active():
            return self._pp_step(batch, n_micro=n_micro)
        t_step = self._step_entry()
        trc = _trace.enabled()
        inputs = [b.data if isinstance(b, NDArray) else jnp.asarray(b)
                  for b in batch]
        bax = self._eff_bax(inputs[-1].ndim, is_label=True)
        if inputs[-1].shape[bax] % n_micro:
            raise MXNetError(
                f"step_accum: batch axis {bax} size "
                f"{inputs[-1].shape[bax]} not divisible by n_micro "
                f"{n_micro}")
        if self._param_objs is None:
            # one-microbatch probe resolves deferred shapes (sliced on
            # each input's own effective batch axis); skipped once
            # params exist
            probe = [NDArray(jnp.take(
                b, jnp.arange(max(1, b.shape[self._eff_bax(b.ndim)]
                                  // n_micro)),
                axis=self._eff_bax(b.ndim))) for b in inputs[:-1]]
            params = self._collect(*probe)
        else:
            params = self._param_objs
        if self._zero1_active():
            self._zero1_ensure_plan(inputs)
        self._ensure_device_state(params)
        if self._zero1_active():
            dp = self.mesh.shape[AXIS_DP]
            b = inputs[-1].shape[bax]
            if b % dp or (b // dp) % n_micro:
                raise MXNetError(
                    f"step_accum under shard_updates: batch {b} must "
                    f"split evenly over dp={dp} chips x n_micro="
                    f"{n_micro} microbatches (set MXTPU_SHARDED_SYNC=0 "
                    f"or adjust the batch)")
            jitted = self._get_zero1_jit("accum", inputs, n_micro=n_micro)
        else:
            jitted = self._jit_accum_cache.get(n_micro)
            if jitted is None:
                jitted = self._build_accum(n_micro)
                self._jit_accum_cache[n_micro] = jitted
        tt1 = _trace.clock() if trc else None
        inputs = self._put_batch(inputs)
        tt2 = _trace.clock() if trc else None
        key = _rnd.next_key()
        lr = jnp.asarray(self.learning_rate, jnp.float32)
        new_params, self._opt_state, loss = self._dispatch(
            jitted, self._param_vals, self._opt_state, lr, key, *inputs)
        tt3 = _trace.clock() if trc else None
        self._num_update += 1
        self._param_vals = list(new_params)
        for p, v in zip(params, new_params):
            p._data._set_data(v)
        self._record_step(1, t_step)
        if trc:
            self._trace_step_phases(tt1, tt2, tt3)
        return NDArray(loss)

    # -- ZeRO-1 sharded gradient sync (the bucketed RS+AG pipeline) -----
    def _zero1_active(self):
        """Resolve (once) whether the sharded pipeline runs: needs
        ``shard_updates=True``, dp > 1, the kill switch off, an
        elementwise update rule (sgd/nag/adam/adamw/rmsprop — lamb/lars
        need per-parameter norms and keep the psum path), and pure data
        parallelism (any tp-sharded parameter falls back)."""
        if self._zero1 is None:
            self._zero1 = (
                self._shard_updates
                and _zero.sharded_sync_enabled()
                and self._rule_name in _zero.ZERO1_RULES
                and self._param_objs is not None
                and all(p.shard_spec is None for p in self._param_objs))
        return self._zero1

    def _zero1_ensure_plan(self, probe_inputs=None):
        """Build the bucket plan once.  With overlap on and a batch
        signature available, the fill order is the REVERSE of the
        forward parameter-use order (one abstract trace, no FLOPs) —
        buckets then complete early-to-late during the XLA backward, so
        each bucket's reduce-scatter is data-ready long before the
        backward finishes and the latency-hiding scheduler
        (``MXTPU_LHS=1``) can sink it under the remaining compute.
        ``MXTPU_OVERLAP_COMM=0`` (or no batch: checkpoint restore)
        keeps PR 3's declaration-order fill bitwise."""
        if self._plan is None:
            order = None
            if self._overlap_comm and probe_inputs is not None:
                order = self._probe_backward_order(probe_inputs)
            self._plan = _zero.BucketPlan(
                [tuple(p.shape) for p in self._param_objs],
                self.mesh.shape[AXIS_DP], fill_order=order)
        return self._plan

    def _probe_backward_order(self, inputs):
        """Parameter indices in expected backward gradient-ready order:
        record first-use order over ONE abstract forward
        (``jax.eval_shape`` — trace only, nothing computes) and reverse
        it.  Returns None (declaration order) if the probe cannot run."""
        from ..gluon.parameter import record_param_use
        params = self._param_objs
        # the abstract forward can WRITE tracers into parameter state
        # (batch-norm running stats update through _set_data during the
        # trace); snapshot the raw buffers and restore unconditionally,
        # or the leaked tracers blow up the next device_put
        snapshot = [(p._data, p._data._data) for p in params
                    if p._data is not None]
        try:
            loss_of = self._make_loss_of()

            def struct(a):
                return jax.ShapeDtypeStruct(tuple(a.shape), a.dtype)

            pv = [jax.ShapeDtypeStruct(tuple(p.shape),
                                       p.data().data.dtype)
                  for p in params]
            rec = record_param_use()
            with rec:
                jax.eval_shape(
                    loss_of, pv, jax.random.PRNGKey(0),
                    [struct(b) for b in inputs[:-1]], struct(inputs[-1]))
            pos = {id(p): i for i, p in enumerate(params)}
            used = [pos[id(p)] for p in rec.order if id(p) in pos]
            rest = [i for i in range(len(params)) if i not in set(used)]
            # params used EARLIEST in forward get their grads LAST;
            # never-used params (frozen branches) go to the tail buckets
            return list(reversed(used)) + rest if used else None
        except Exception:  # noqa: BLE001 — the probe is an optimization,
            # never a correctness gate; declaration order always works
            return None
        finally:
            for arr, raw in snapshot:
                arr._data = raw

    def _zero1_state_spec_tree(self):
        """shard_map specs for the bucket optimizer state: vector leaves
        (per-element state) shard over 'dp', scalar leaves (step
        counters) replicate."""
        return jax.tree.map(
            lambda x: P(AXIS_DP) if getattr(x, "ndim", 0) >= 1 else P(),
            self._opt_state)

    def _zero1_sync_update(self, param_vals, grads, opt_local, lr, key,
                           comm_mode="overlap"):
        """Bucketed reduce-scatter -> 1/N optimizer update -> all-gather.
        Runs INSIDE shard_map ('dp' bound); ``grads`` are this chip's
        LOCAL gradients, ``opt_local`` the local 1/dp state shards.  ONE
        source for plain/accum/multi sharded steps.

        ``comm_mode`` exists for the with-vs-without-overlap probe
        (:meth:`overlap_probe`):

        - ``"overlap"`` (the training path): each bucket's flat gradient
          — and therefore its reduce-scatter — is data-dependent ONLY on
          that bucket's own parameters' grads, so with a backward-ordered
          plan the latency-hiding scheduler can launch bucket b's
          collective while buckets b+1.. are still in backward compute.
        - ``"mono"``: an ``optimization_barrier`` ties every bucket's
          payload to ALL gradients and chains the buckets, modeling the
          PR 3 all-comm-after-backward schedule.
        - ``"none"``: collectives replaced by shape-identical local ops
          (slice / tile) — the pure-compute baseline the probe subtracts.
        """
        plan = self._plan
        dp = self.mesh.shape[AXIS_DP]
        mode = self._comm_dtype
        idx = lax.axis_index(AXIS_DP)
        gflats = plan.flatten(grads)
        pflats = plan.flatten(param_vals)
        if comm_mode == "mono":
            # every bucket now depends on the WHOLE backward
            gflats = list(lax.optimization_barrier(tuple(gflats)))
        new_pflats, new_state = [], []
        prev_shard = None
        for b in range(plan.n_buckets):
            ls = plan.shard_length(b)
            gflat = gflats[b]
            if comm_mode == "mono" and prev_shard is not None:
                # serialize bucket b's collective behind bucket b-1's
                gflat, _ = lax.optimization_barrier((gflat, prev_shard))
            if comm_mode == "none":
                gshard = lax.dynamic_slice(gflat, (idx * ls,), (ls,))
            else:
                with jax.named_scope("train.allreduce"):
                    gshard = _zero.reduce_scatter_bucket(
                        gflat, jax.random.fold_in(key, b), dp, mode)
            prev_shard = gshard
            pshard = lax.dynamic_slice(pflats[b], (idx * ls,), (ls,))
            # flat 1/N shard update: ONE fused kernel walks the bucket
            # (Pallas on TPU, the identical fused_rule chain elsewhere)
            with jax.named_scope("train.update"):
                np_, ns = self._bucket_apply(pshard, gshard, opt_local[b],
                                             lr)
            if comm_mode == "none":
                new_pflats.append(jnp.tile(np_, dp))
            else:
                with jax.named_scope("train.allreduce"):
                    new_pflats.append(
                        lax.all_gather(np_, AXIS_DP, tiled=True))
            new_state.append(ns)
        return plan.unflatten(new_pflats, param_vals), new_state

    def _get_zero1_jit(self, kind, inputs, n_micro=None, n_steps=None,
                       comm_mode="overlap", donate=None):
        """Build (and cache per input-rank signature) the jitted
        shard_map step.  Unlike the psum path, shard_map needs the
        in/out specs — hence ranks — up front; jit would retrace per
        shape anyway, so this costs nothing extra."""
        self._zero1_ensure_plan()
        sig = (kind, n_micro, n_steps, tuple(b.ndim for b in inputs),
               comm_mode, donate)
        jitted = self._jit_zero1_cache.get(sig)
        if jitted is not None:
            return jitted
        mesh = self.mesh
        n_in = len(inputs)
        grad_fn = self._grad_fn(self._make_loss_of(manual=True),
                                n_micro if kind in ("accum", "multi")
                                and n_micro else 1)

        def local_step(param_vals, opt_local, lr, key, ins, label):
            """One sharded step: per-chip grads -> pmean loss -> the
            bucketed RS -> 1/N update -> AG pipeline.  Shared by every
            kind; the multi-step scan body IS this function."""
            # per-chip PRNG stream (dropout etc. draws fresh per chip)
            key = jax.random.fold_in(key, lax.axis_index(AXIS_DP))
            grads, loss = grad_fn(param_vals, key, ins, label)
            loss = lax.pmean(loss, AXIS_DP)
            new_params, new_state = self._zero1_sync_update(
                param_vals, grads, opt_local, lr,
                jax.random.fold_in(key, 0x5eed), comm_mode=comm_mode)
            return new_params, new_state, loss

        if kind == "multi":
            def local_body(param_vals, opt_local, lrs, keys, *stacked):
                def one_step(carry, xs):
                    pv, st = carry
                    lr, key = xs[0], xs[1]
                    batch = list(xs[2:])
                    new_p, new_s, loss = local_step(
                        pv, st, lr, key, batch[:-1], batch[-1])
                    return (new_p, new_s), loss

                (pv, st), losses = lax.scan(
                    one_step, (list(param_vals), opt_local),
                    (lrs, keys) + tuple(stacked))
                return pv, st, losses
        else:
            def local_body(param_vals, opt_local, lr, key, *batch):
                return local_step(param_vals, opt_local, lr, key,
                                  list(batch[:-1]), batch[-1])

        pspecs = [P()] * len(self._param_vals)
        sspecs = self._zero1_state_spec_tree()
        if kind == "multi":
            # per-step batches stacked on a leading replicated K axis;
            # the within-batch sharding follows the same _eff_bax rule
            batch_specs = tuple(
                self._stacked_spec(b.ndim + 1, is_label=(i == n_in - 1))
                for i, b in enumerate(inputs))
        else:
            batch_specs = tuple(
                self._batch_spec(b.ndim, is_label=(i == n_in - 1))
                for i, b in enumerate(inputs))
        in_specs = (pspecs, sspecs, P(), P()) + batch_specs
        out_specs = (pspecs, sspecs, P())
        wrapped = shard_map(local_body, mesh=mesh, in_specs=in_specs,
                            out_specs=out_specs, check_vma=False)
        if donate is None:
            donate = self._donate
        jitted = jax.jit(wrapped,
                         donate_argnums=(0, 1) if donate else ())
        self._jit_zero1_cache[sig] = jitted
        return jitted

    def _zero1_check_batch(self, inputs):
        dp = self.mesh.shape[AXIS_DP]
        for i, b in enumerate(inputs):
            ax = self._eff_bax(b.ndim, is_label=(i == len(inputs) - 1))
            if b.shape[ax] % dp:
                raise MXNetError(
                    f"shard_updates: batch axis {ax} size {b.shape[ax]} "
                    f"not divisible by dp={dp} (the sharded pipeline "
                    f"needs even shards; MXTPU_SHARDED_SYNC=0 restores "
                    f"the psum path)")

    # -- pipeline parallelism (ISSUE 11: pp axis of the MeshConfig) -----
    def _pp_active(self):
        return self.mesh_config.pp > 1

    def _pp_ensure(self):
        """Build the 1F1B stage executor once: split the block into
        ``pp`` contiguous stages and give each its ``dp [x tp]``
        submesh (``MeshConfig.stage_mesh``) — stage params/optimizer
        state live ONLY there."""
        if self._pp_exec is None:
            from .pipeline_parallel import (PipelineStageExecutor,
                                            split_into_stages)
            stages = split_into_stages(self.block, self.mesh_config.pp)
            devices = list(_np.asarray(self.mesh.devices).reshape(-1))
            self._pp_exec = PipelineStageExecutor(
                stages, self.loss_fn, self.mesh_config, devices,
                self._rule_init, self._rule_apply,
                self._pp_microbatches)
        return self._pp_exec

    def _pp_step(self, batch, n_micro=1):
        """One pp training step (step/step_accum/step_multi all land
        here): the executor runs M = pp_microbatches * n_micro
        microbatches through the 1F1B schedule.  Loss semantics match
        the flat step: the mean of equal-size microbatch means IS the
        full-batch mean."""
        t_step = self._step_entry()
        if self.batch_axis != 0 or self._label_bax != 0:
            raise MXNetError(
                "pipeline parallelism supports batch_axis=0 only")
        inputs = [b.data if isinstance(b, NDArray) else jnp.asarray(b)
                  for b in batch]
        if len(inputs) != 2:
            raise MXNetError(
                "pipeline parallelism expects (data, label) batches — "
                "a Sequential stage chain has one activation stream")
        self._collect(NDArray(inputs[0]))
        ex = self._pp_ensure()
        key = _rnd.next_key()
        lr = self.learning_rate
        trc = _trace.enabled()
        tt0 = _trace.clock() if trc else None
        loss = ex.step(inputs[0], inputs[1], key, lr, n_micro=n_micro)
        self._num_update += 1
        self._record_step(1, t_step)
        if trc:
            # host-driven 1F1B: the stage executor owns the inner
            # schedule, so the step is one dispatch-phase child of the
            # ambient train.step root
            _trace.annotate(_trace.current(), step=self._num_update,
                            pp=True)
            _trace.record("train.phase.dispatch", tt0, _trace.clock())
        return NDArray(loss)

    # -- telemetry (ISSUE 9) --------------------------------------------
    def _dispatch(self, jitted, *args):
        """Run one compiled step dispatch, timed into the telemetry
        registry (``train.dispatch_ms`` — HOST dispatch time; jax
        returns before the device finishes, so device time lives in the
        profiler/XLA trace, not here).  An unhandled dispatch exception
        dumps the flight recorder before re-raising."""
        t0 = _telem.clock() if _telem.enabled() else None
        try:
            out = jitted(*args)
        except Exception as e:  # noqa: BLE001 — record, then re-raise
            _telem.on_step_error(self._num_update, e)
            raise
        if _donation._ENABLED and self._donate:
            # every step variant donates positions (0, 1) — the param
            # and optimizer-state buffers are dead past this point; any
            # later host touch of them is the TPU crash, caught on CPU
            _donation.poison(args[:2],
                             site="DataParallelTrainer._dispatch")
        if t0 is not None:
            _telem.observe("train.dispatch_ms",
                           (_telem.clock() - t0) * 1e3)
        return out

    def _step_entry(self):
        """The telemetry clock at a step's entry (None when telemetry is
        off), after observing ``train.step_interval_ms``: the time since
        the previous entry, which in a device-bound loop is the step
        time, whereas ``train.step_ms`` is the host's time inside the
        call."""
        if not _telem.enabled():
            return None
        t = _telem.clock()
        if self._last_entry is not None:
            _telem.observe("train.step_interval_ms",
                           (t - self._last_entry) * 1e3)
        self._last_entry = t
        return t

    def _record_step(self, k, t_step0):
        """Publish per-step metrics after ``k`` steps committed; the
        ambient telemetry step context feeds event records and profiler
        span tags; the health watchdog ticks at the same seam."""
        if self._mem_gauges_stale:
            self._publish_memory_gauges()
        if t_step0 is None:
            return
        dt_s = _telem.clock() - t_step0
        _telem.set_context(step=self._num_update)
        _telem.inc("train.steps", k)
        _telem.observe("train.step_ms", dt_s * 1e3 / max(k, 1))
        _telem.set_gauge("train.num_update", self._num_update)
        _watchdog.on_step(self._num_update,
                          step_ms=dt_s * 1e3 / max(k, 1))

    def _publish_memory_gauges(self):
        """One-time (per build) exact byte gauges, also carried as
        arguments of the ``train.step`` root span that is ambient (the
        first step's): the device-resident param bytes this trainer owns
        (``train.param_bytes``), its per-chip optimizer-state bytes
        (``train.state_bytes``; for the flight recorder's ``memory``
        block (ISSUE 15) also under ``train.zero1_shard_bytes`` when
        ZeRO-1 shards it, ``train.opt_state_bytes`` otherwise) and the
        bytes of the gradient arrays the eager tape holds for the
        network's parameters (``autograd.grad_buffer_bytes``: 0 unless
        something ran an eager backward or read ``.grad`` — the fused
        step keeps its gradients inside the program).  Exact arithmetic
        on shapes already in hand — no device traffic."""
        self._mem_gauges_stale = False
        try:
            pbytes = sbytes = 0
            if self._param_vals is not None:
                pbytes = sum(leaf.size * leaf.dtype.itemsize
                             for leaf in jax.tree.leaves(self._param_vals))
                _telem.set_gauge("train.param_bytes", int(pbytes))
            if self._opt_state is not None:
                dp = self.mesh.shape.get(AXIS_DP, 1)
                zero1 = bool(self._zero1 and self._plan is not None)
                for leaf in jax.tree.leaves(self._opt_state):
                    nbytes = leaf.size * leaf.dtype.itemsize
                    # ZeRO-1: vector leaves are dp-sharded, scalars
                    # replicate (the comm_stats accounting)
                    sbytes += nbytes // dp if zero1 and leaf.ndim >= 1 \
                        else nbytes
                _telem.set_gauge("train.zero1_shard_bytes" if zero1
                                 else "train.opt_state_bytes",
                                 int(sbytes))
                _telem.set_gauge("train.state_bytes", int(sbytes))
            gbytes = sum(_tape.grad_bytes(p._data)
                         for p in self._param_objs or ()
                         if p._data is not None)
            _telem.set_gauge("autograd.grad_buffer_bytes", int(gbytes))
            _trace.annotate(_trace.current(), param_bytes=int(pbytes),
                            state_bytes=int(sbytes),
                            grad_buffer_bytes=int(gbytes))
        except Exception:  # noqa: BLE001 — observability never takes
            pass           # a training step down

    def _trace_step_phases(self, t1, t2, t3, batch=()):
        """Commit the four pre-timed children that tile the ambient
        ``train.step`` root (:func:`_step_span`) from its start to now —
        prepare (param collect / plan / device state), h2d (batch
        placement), dispatch (the compiled call), commit (host-side
        param bookkeeping + metric publication).  A ``batch`` that came
        out of a ``DevicePrefetcher`` gives the root its number there,
        the ``batch`` of its ``io.batch`` and ``io.wait`` spans."""
        root = _trace.current()
        _trace.annotate(root, step=self._num_update)
        io_batch = getattr(batch[0], "_io_batch", None) if batch else None
        if io_batch is not None:
            _trace.annotate(root, batch=io_batch)
        _trace.record("train.phase.prepare", root.t0, t1)
        _trace.record("train.phase.h2d", t1, t2)
        _trace.record("train.phase.dispatch", t2, t3)
        _trace.record("train.phase.commit", t3, _trace.clock())

    # -- public API -----------------------------------------------------
    @property
    def learning_rate(self):
        if self._lr_scheduler is not None:
            return self._lr_scheduler(self._num_update)
        return self._lr

    def set_learning_rate(self, lr):
        self._lr = lr

    @_step_span
    def step(self, *batch):
        """batch = (*inputs, label) NDArrays. Returns the scalar loss
        NDArray."""
        if self._pp_active():
            return self._pp_step(batch)
        t_step = self._step_entry()
        trc = _trace.enabled()
        inputs = [b.data if isinstance(b, NDArray) else jnp.asarray(b)
                  for b in batch]
        params = self._collect(*[NDArray(b) for b in inputs[:-1]])
        if self._zero1_active():
            # plan BEFORE device state: the bucket-sharded optimizer
            # state is laid out in plan (fill-order) space
            self._zero1_ensure_plan(inputs)
        self._ensure_device_state(params)
        if self._zero1_active():
            self._zero1_check_batch(inputs)
            jitted = self._get_zero1_jit("plain", inputs)
        else:
            if self._jitted is None:
                self._build()
            jitted = self._jitted
        tt1 = _trace.clock() if trc else None
        inputs = self._put_batch(inputs)
        tt2 = _trace.clock() if trc else None
        key = _rnd.next_key()
        lr = jnp.asarray(self.learning_rate, jnp.float32)
        new_params, self._opt_state, loss = self._dispatch(
            jitted, self._param_vals, self._opt_state, lr, key, *inputs)
        tt3 = _trace.clock() if trc else None
        self._num_update += 1
        self._param_vals = list(new_params)
        for p, v in zip(params, new_params):
            p._data._set_data(v)
        self._record_step(1, t_step)
        if trc:
            self._trace_step_phases(tt1, tt2, tt3, batch)
        return NDArray(loss)

    @_step_span
    def step_multi(self, batches, n_micro=1):
        """K training steps in ONE compiled dispatch (ISSUE 6 tentpole).

        ``batches``: sequence of K per-step batches, each the same
        ``(*inputs, label)`` tuple :meth:`step` takes (all K must share
        shapes — the scan is one trace).  ``n_micro`` > 1 composes with
        in-graph gradient accumulation: each of the K steps is itself a
        ``step_accum``-style microbatch scan.  Returns the (K,) vector
        of per-step losses as one NDArray — read it AFTER the dispatch
        returns; one host sync per K steps is the point.

        Bitwise contract: K steps through here produce exactly the
        params/optimizer state/losses of K consecutive ``step`` (or
        ``step_accum``) calls — per-step lrs and PRNG keys are drawn
        host-side from the same streams, and the step body is the same
        ``_grad_fn``/update code.  ``MXTPU_STEPS_PER_CALL=1`` (the
        default) keeps K-aware loops (``estimator.fit``) on the per-step
        entry points, restoring today's graphs exactly.
        """
        t_step = self._step_entry()
        trc = _trace.enabled()
        batches = list(batches)
        k = len(batches)
        if k < 1:
            raise MXNetError("step_multi: need at least one batch")
        if n_micro < 1:
            raise MXNetError("step_multi: n_micro must be >= 1")
        if self._pp_active():
            # the pp schedule is host-driven — K steps run as K
            # consecutive 1F1B windows (identical math to K=1 by
            # construction; the scan fusion is a flat-mesh feature)
            losses = [self._pp_step(bt, n_micro=n_micro).data
                      for bt in batches]
            return NDArray(jnp.stack(losses))
        steps = [[b.data if isinstance(b, NDArray) else jnp.asarray(b)
                  for b in bt] for bt in batches]
        first = steps[0]
        n_in = len(first)
        for s in steps[1:]:
            if len(s) != n_in or any(
                    tuple(a.shape) != tuple(b.shape) or a.dtype != b.dtype
                    for a, b in zip(s, first)):
                raise MXNetError(
                    "step_multi: all K batches must share shapes/dtypes "
                    "(one scan trace covers the whole window)")
        bax = self._eff_bax(first[-1].ndim, is_label=True)
        if first[-1].shape[bax] % n_micro:
            raise MXNetError(
                f"step_multi: batch axis {bax} size "
                f"{first[-1].shape[bax]} not divisible by n_micro "
                f"{n_micro}")
        params = self._collect(*[NDArray(b) for b in first[:-1]])
        if self._zero1_active():
            self._zero1_ensure_plan(first)
        self._ensure_device_state(params)
        if self._zero1_active():
            self._zero1_check_batch(first)
            dp = self.mesh.shape[AXIS_DP]
            if n_micro > 1 and (first[-1].shape[bax] // dp) % n_micro:
                raise MXNetError(
                    f"step_multi under shard_updates: batch "
                    f"{first[-1].shape[bax]} must split evenly over "
                    f"dp={dp} chips x n_micro={n_micro} microbatches")
            jitted = self._get_zero1_jit("multi", first, n_micro=n_micro,
                                         n_steps=k)
        else:
            jitted = self._jit_multi_cache.get((k, n_micro))
            if jitted is None:
                jitted = self._build_multi(k, n_micro)
                self._jit_multi_cache[(k, n_micro)] = jitted
        tt1 = _trace.clock() if trc else None
        stacked = self._put_stacked(steps)
        tt2 = _trace.clock() if trc else None
        # per-step keys/lrs drawn from the SAME host streams the K=1
        # path uses — this is what makes K>1 bitwise-match K=1
        keys = jnp.stack([_rnd.next_key() for _ in range(k)])
        if self._lr_scheduler is not None:
            lrs = [float(self._lr_scheduler(self._num_update + i))
                   for i in range(k)]
        else:
            lrs = [self._lr] * k
        lrs = jnp.asarray(lrs, jnp.float32)
        new_params, self._opt_state, losses = self._dispatch(
            jitted, self._param_vals, self._opt_state, lrs, keys,
            *stacked)
        tt3 = _trace.clock() if trc else None
        self._num_update += k
        self._param_vals = list(new_params)
        for p, v in zip(params, new_params):
            p._data._set_data(v)
        self._record_step(k, t_step)
        if trc:
            self._trace_step_phases(tt1, tt2, tt3)
        return NDArray(losses)

    def _ensure_device_state(self, params):
        """Params stay resident on device across steps (VERDICT r1 weak
        #6: re-device_put per step put a host round on the timed path).
        Only a parameter externally mutated since our last write (identity
        check against the cached array) is re-transferred."""
        if self._pp_active():
            # pp-staged state lives with the stage executor (each
            # stage's submesh), not in the flat-mesh caches
            self._pp_ensure().ensure_ready()
            return
        if self._param_vals is None:
            self._param_vals = [
                jax.device_put(p.data().data, self._param_sharding(p))
                for p in params]
        else:
            for i, p in enumerate(params):
                if p._data is not None and \
                        p._data._data is not self._param_vals[i]:
                    self._param_vals[i] = jax.device_put(
                        p.data().data, self._param_sharding(p))
        if self._opt_state is None:
            if self._zero1_active():
                # ZeRO-1: optimizer state lives in BUCKET space, each
                # vector leaf a flat (bucket_len,) array physically
                # sharded 1/dp per chip; scalar leaves (step counters)
                # replicate.  This is where the (N-1)/N optimizer-HBM
                # saving comes from.
                plan = self._zero1_ensure_plan()
                shard = NamedSharding(self.mesh, P(AXIS_DP))
                rep = NamedSharding(self.mesh, P())
                self._opt_state = [
                    jax.tree.map(
                        lambda x: jax.device_put(
                            x, shard if getattr(x, "ndim", 0) >= 1
                            else rep),
                        self._rule_init(
                            jnp.zeros((plan.lengths[b],), jnp.float32)))
                    for b in range(plan.n_buckets)]
            else:
                rep = NamedSharding(self.mesh, P())
                self._opt_state = [
                    jax.tree.map(lambda x: jax.device_put(x, rep),
                                 self._rule_init(v))
                    for v in self._param_vals]

    # -- elastic membership (mx.elastic, ISSUE 8) -----------------------
    def rebuild(self, mesh):
        """Adopt a new mesh **in place** — the trainer half of an
        elastic reshard (``checkpoint.reshard_in_place`` drives the full
        save-state / rebuild / restore-state sequence).

        Everything derived from the old world size is dropped: the
        ZeRO-1 resolution and :class:`~mxnet_tpu.parallel.zero.BucketPlan`
        (bucket padding divides the dp size, so the plan cannot
        survive), every compiled step (jit caches — the traced programs
        bake the old mesh), and the device-resident params/optimizer
        state (sharded over devices that may no longer be in the mesh).
        Parameters stay in the block and are re-placed on first use;
        optimizer state does NOT survive — reload it via
        :meth:`load_state_dict` (its on-disk/per-parameter form is
        dp-independent by PR 4 design, so any source dp reshards
        bitwise).  The update-counter and lr schedule state are host
        scalars and carry over untouched.

        ``mesh`` may be a ``jax.sharding.Mesh`` or a
        :class:`~mxnet_tpu.parallel.mesh.MeshConfig` — an elastic
        transition re-fences ALL THREE axes through here, not just dp
        (ISSUE 11): the pp stage executor, tp shard placements and the
        ZeRO resolution are all re-derived from the new config."""
        if isinstance(mesh, MeshConfig):
            cfg = mesh.resolve(len(jax.devices()))
            mesh = cfg.build()
        else:
            cfg = MeshConfig.for_mesh(mesh)
        self.mesh = mesh
        self.mesh_config = cfg
        self._pp_exec = None
        self._shard_updates = self._shard_requested and \
            mesh.shape.get(AXIS_DP, 1) > 1 and \
            cfg.tp == 1 and cfg.pp == 1
        self._zero1 = None
        self._plan = None
        self._jitted = None
        self._jit_accum_cache = {}
        self._jit_multi_cache = {}
        self._jit_zero1_cache = {}
        self._param_vals = None
        self._opt_state = None
        self._mem_gauges_stale = True
        return self

    # -- checkpoint protocol (mx.checkpoint.CheckpointManager) ----------
    def _require_params(self):
        if self._param_objs is None:
            params = sorted(self.block.collect_params().items())
            if any(p._data is None for _, p in params):
                raise MXNetError(
                    "DataParallelTrainer state restore needs resolved "
                    "parameter shapes: restore the net's parameters "
                    "first (CheckpointManager does params before "
                    "trainer) or run one forward")
            self._param_objs = [p for _, p in params]
        self._ensure_device_state(self._param_objs)
        return self._param_objs

    def state_dict(self):
        """Optimizer state in PER-PARAMETER space — dp-independent, so a
        resumed run with a different dp size (or with ``shard_updates``
        toggled) rebuckets/reshards on load instead of being stuck with
        the saved topology.  ZeRO-1 bucket vectors are sliced back to
        per-parameter arrays (the D2H gathers the 1/dp shards); bucket
        scalars (e.g. Adam's ``t``) are identical across buckets and
        saved once."""
        from ..ndarray.ndarray import NDArray as _ND
        arrays, leaves = {}, {}
        if self._pp_active():
            # pp-staged state: the executor's per-stage trees map back
            # to the global (sorted) parameter index — the on-disk form
            # is identical to the replicated save, so a checkpoint
            # written at dp x tp x pp restores into ANY mesh shape
            ex = self._pp_exec
            if ex is not None and ex._opt_state is not None and \
                    self._param_objs is not None:
                pos = {id(p): i for i, p in enumerate(self._param_objs)}
                for _s, _li, p, _val, state in ex.iter_params():
                    gi = pos[id(p)]
                    for name, leaf in state.items():
                        if getattr(leaf, "ndim", 0) >= 1:
                            arrays[f"opt/{gi}/{name}"] = _ND(leaf)
                            leaves[name] = "vec"
                        else:
                            arrays[f"opt/{gi}/{name}"] = _ND(
                                jnp.asarray(leaf))
                            leaves.setdefault(name, "per_param_scalar")
        elif self._opt_state is not None:
            params = self._param_objs
            if self._zero1_active():
                plan = self._zero1_ensure_plan()
                full = {}       # bucket id -> {leaf: host flat vector}
                for b, state_b in enumerate(self._opt_state):
                    full[b] = {}
                    for name, leaf in state_b.items():
                        if getattr(leaf, "ndim", 0) >= 1:
                            full[b][name] = _np.asarray(
                                jax.device_get(leaf))
                            leaves[name] = "vec"
                        elif name not in leaves:
                            arrays[f"opt_scalar/{name}"] = _ND(
                                jnp.asarray(leaf))
                            leaves[name] = "scalar"
                for i, p in enumerate(params):
                    b, off, n = plan.param_span(i)
                    for name, vec in full[b].items():
                        arrays[f"opt/{i}/{name}"] = _ND(jnp.asarray(
                            vec[off:off + n].reshape(plan.shapes[i])))
            else:
                for i, state in enumerate(self._opt_state):
                    for name, leaf in state.items():
                        if getattr(leaf, "ndim", 0) >= 1:
                            arrays[f"opt/{i}/{name}"] = _ND(leaf)
                            leaves[name] = "vec"
                        else:
                            arrays[f"opt/{i}/{name}"] = _ND(
                                jnp.asarray(leaf))
                            leaves.setdefault(name, "per_param_scalar")
        meta = {"kind": "parallel.DataParallelTrainer",
                "rule": self._rule_name,
                "num_update": int(self._num_update),
                "saved_dp": int(self.mesh.shape.get(AXIS_DP, 1)),
                "saved_mesh": self.mesh_config.describe(),
                "zero1": bool(self._opt_state is not None
                              and self._zero1_active()),
                "leaves": leaves}
        return {"arrays": arrays, "meta": meta}

    def load_state_dict(self, d):
        """Inverse of :meth:`state_dict`, resharding for THIS trainer's
        topology: under ZeRO-1 the per-parameter arrays are re-flattened
        into this dp size's bucket plan (padding recomputed) and
        device_put 1/dp-sharded; replicated mode loads per-parameter
        trees.  A checkpoint saved at dp=8 restores at dp=2 (or 1) and
        vice versa."""
        arrays, meta = d["arrays"], d["meta"]
        self._num_update = int(meta.get("num_update", 0))
        leaves = meta.get("leaves", {})
        if not leaves:
            return                  # no optimizer state yet at save time
        params = self._require_params()

        def host(a):
            return _np.asarray(a.asnumpy())

        if self._pp_active():
            # re-stage the per-parameter state onto each stage's submesh
            # (the pp inverse of the branches below; a checkpoint saved
            # at ANY mesh shape — flat dp8, zero1, 2x2x2 — lands here
            # when THIS trainer has a pipeline axis)
            ex = self._pp_ensure()
            ex.ensure_ready()
            pos = {id(p): i for i, p in enumerate(params)}
            for s, li, p, val, _state in list(ex.iter_params()):
                gi = pos[id(p)]
                tmpl = self._rule_init(val)
                new_state = {}
                for name, tleaf in tmpl.items():
                    if tleaf.ndim >= 1:
                        src = host(arrays[f"opt/{gi}/{name}"])
                        new_state[name] = jnp.asarray(
                            src, tleaf.dtype).reshape(tleaf.shape)
                    else:
                        key = f"opt/{gi}/{name}" \
                            if f"opt/{gi}/{name}" in arrays \
                            else f"opt_scalar/{name}"
                        new_state[name] = jnp.asarray(
                            host(arrays[key]).reshape(()), tleaf.dtype)
                ex.set_state(s, li, new_state)
            ex.ensure_ready()       # re-place the restored params
            return

        if self._zero1_active():
            plan = self._zero1_ensure_plan()
            shard = NamedSharding(self.mesh, P(AXIS_DP))
            rep = NamedSharding(self.mesh, P())
            # template fixes the leaf set + dtypes for this rule
            template = self._rule_init(jnp.zeros((1,), jnp.float32))
            new_state = []
            for b in range(plan.n_buckets):
                state_b = {}
                for name in template:
                    if leaves.get(name) == "vec":
                        flat = _np.zeros((plan.lengths[b],), _np.float32)
                        for i in plan.buckets[b]:
                            _, off, n = plan.param_span(i)
                            flat[off:off + n] = host(
                                arrays[f"opt/{i}/{name}"]).reshape(-1)
                        state_b[name] = jax.device_put(
                            jnp.asarray(flat), shard)
                    else:
                        # bucket scalar: ``opt_scalar/<name>`` (zero1
                        # save) or any per-param copy (replicated save —
                        # all params share the value, e.g. Adam's t)
                        key = f"opt_scalar/{name}" \
                            if f"opt_scalar/{name}" in arrays \
                            else f"opt/0/{name}"
                        val = host(arrays[key]).reshape(())
                        state_b[name] = jax.device_put(
                            jnp.asarray(val, template[name].dtype), rep)
                new_state.append(state_b)
            self._opt_state = new_state
        else:
            rep = NamedSharding(self.mesh, P())
            new_state = []
            for i, v in enumerate(self._param_vals):
                template = self._rule_init(v)
                state_i = {}
                for name, tleaf in template.items():
                    if tleaf.ndim >= 1:
                        src = host(arrays[f"opt/{i}/{name}"])
                        state_i[name] = jax.device_put(
                            jnp.asarray(src, tleaf.dtype).reshape(
                                tleaf.shape), rep)
                    else:
                        key = f"opt/{i}/{name}" \
                            if f"opt/{i}/{name}" in arrays \
                            else f"opt_scalar/{name}"
                        state_i[name] = jax.device_put(
                            jnp.asarray(host(arrays[key]).reshape(()),
                                        tleaf.dtype), rep)
                new_state.append(state_i)
            self._opt_state = new_state
        # params themselves were restored into the block; re-place them
        # on the mesh so the next step starts from the restored values
        self._param_vals = [
            jax.device_put(p.data().data, self._param_sharding(p))
            for p in params]

    # -- observability ---------------------------------------------------
    def compiled_step_text(self, *batch):
        """The optimized HLO of the program :meth:`step` runs for this
        batch signature, as the compiler left it: what a check reads to
        see that a kernel (a Mosaic ``tpu_custom_call`` and its name)
        or a collective really is in the step.  Call after a step; it
        compiles once more, or hits the persistent compile cache.
        Trainer state is untouched."""
        inputs = [b.data if isinstance(b, NDArray) else jnp.asarray(b)
                  for b in batch]
        if self._param_vals is None:
            raise MXNetError("compiled_step_text: run one step() first")
        jitted = self._get_zero1_jit("plain", inputs) \
            if self._zero1_active() else self._jitted
        return jitted.lower(
            self._param_vals, self._opt_state,
            jnp.asarray(self.learning_rate, jnp.float32),
            jax.random.key(0), *self._put_batch(inputs)
        ).compile().as_text()

    def overlap_probe(self, *batch, iters=5):
        """The with-vs-without-overlap probe (ISSUE 5): time three
        structurally different builds of THIS trainer's sharded step on
        ``batch`` —

        - *overlapped* (the training graph): per-bucket reduce-scatter
          data-dependent only on its own grads, free to ride under
          backward compute;
        - *monolithic*: ``optimization_barrier`` pins every collective
          behind the whole backward and chains the buckets (the PR 3
          schedule);
        - *compute-only*: collectives swapped for shape-identical local
          ops — the baseline both are measured against.

        Returns ``exposed_comm_ms`` (comm left on the overlapped step's
        critical path) and ``overlap_frac`` (share of the serialized
        comm the overlap hides: ``1 - exposed / (mono - compute)``).
        All probe programs are compiled WITHOUT donation, so trainer
        state is untouched.  Zeros when the sharded pipeline is off
        (CPU / dp=1 / kill switch)."""
        import time
        # None = NOT measured (pipeline off) — a 0.0 here would read as
        # "measured: comm is free", which the r04/r05 CPU-fallback rounds
        # showed gets mistaken for evidence
        out = {"exposed_comm_ms": None, "overlap_frac": None,
               "overlapped_step_ms": None, "monolithic_step_ms": None,
               "compute_only_step_ms": None}
        inputs = [b.data if isinstance(b, NDArray) else jnp.asarray(b)
                  for b in batch]
        params = self._collect(*[NDArray(b) for b in inputs[:-1]])
        if self._zero1_active():
            self._zero1_ensure_plan(inputs)
        self._ensure_device_state(params)
        if not self._zero1_active() or self.mesh.shape.get(AXIS_DP, 1) <= 1:
            return out
        self._zero1_check_batch(inputs)
        dev_inputs = self._put_batch(inputs)
        key = jax.random.PRNGKey(7)
        lr = jnp.asarray(self.learning_rate, jnp.float32)
        t_all0 = time.perf_counter()
        # tracing the probe variants can write tracers into parameter
        # state (batch-norm running stats update during the trace); the
        # probe discards its results, so restore the raw buffers after —
        # unlike step(), nothing overwrites them with concrete values
        snapshot = [(p._data, p._data._data) for p in params
                    if p._data is not None]
        try:
            for mode, field in (("none", "compute_only_step_ms"),
                                ("overlap", "overlapped_step_ms"),
                                ("mono", "monolithic_step_ms")):
                f = self._get_zero1_jit("plain", inputs, comm_mode=mode,
                                        donate=False)
                res = f(self._param_vals, self._opt_state, lr, key,
                        *dev_inputs)
                jax.block_until_ready(res)      # compile off the clock
                t0 = time.perf_counter()
                for _ in range(iters):
                    res = f(self._param_vals, self._opt_state, lr, key,
                            *dev_inputs)
                jax.block_until_ready(res)
                out[field] = round(
                    (time.perf_counter() - t0) / iters * 1e3, 3)
        finally:
            for arr, raw in snapshot:
                arr._data = raw
        _trace.record("overlap.probe", t_all0, time.perf_counter())
        comp = out["compute_only_step_ms"]
        exposed = max(0.0, out["overlapped_step_ms"] - comp)
        serial = max(exposed, out["monolithic_step_ms"] - comp)
        out["exposed_comm_ms"] = round(exposed, 3)
        if exposed == 0.0:
            # the step DOES contain the collectives (zero1 ran), yet the
            # overlapped build costs no more than pure compute: the comm
            # is fully hidden at this measurement's resolution
            out["overlap_frac"] = 1.0
        elif serial > 0:
            out["overlap_frac"] = round(
                max(0.0, min(1.0, 1.0 - exposed / serial)), 4)
        # retire the probe's private numbers onto the registry: the
        # `comm` block and live scrapers read ONE source (ISSUE 9)
        for field, metric in (("exposed_comm_ms",
                               "train.exposed_comm_ms"),
                              ("overlap_frac", "train.overlap_frac")):
            if out[field] is not None:
                _telem.set_gauge(metric, out[field])
        return out

    def comm_stats(self, measure=False, iters=10, step_ms=None,
                   overlap_stats=None):
        """The per-step ``comm`` block (parallel/zero.py schema): static
        wire accounting always; with ``measure=True`` and dp > 1 the
        collective time is MEASURED by timing a jitted RS+AG-only
        program over this trainer's real bucket shapes (``collective_ms``
        / ``est_ici_gb_s``), and ``overlap_efficiency`` estimates how
        much of it a ``step_ms``-long step could hide.  All fields are
        zeros when the sharded pipeline is off — the schema survives so
        CPU CI regression-tests it (tests/test_sharded_sync.py)."""
        dp = self.mesh.shape.get(AXIS_DP, 1)
        if self._pp_active():
            # pipeline-staged state: each chip holds only its stage's
            # optimizer state (the pp analog of the ZeRO row below)
            ex = self._pp_exec
            total = ex.state_bytes() if ex is not None else 0
            return _zero.comm_block(
                dp=dp, wire_dtype=self._comm_dtype,
                state_bytes_per_chip=total // self.mesh_config.pp,
                state_bytes_replicated=total)
        state_rep = 0
        if self._opt_state is not None:
            for leaf in jax.tree.leaves(self._opt_state):
                state_rep += leaf.size * leaf.dtype.itemsize
        if not (self._zero1 and self._plan is not None):
            # replicated update: every chip carries the full state copy
            state_chip = state_rep
            return _zero.comm_block(
                dp=dp, wire_dtype=self._comm_dtype,
                state_bytes_per_chip=state_chip,
                state_bytes_replicated=state_rep)
        plan = self._plan
        bytes_rs = plan.wire_bytes(self._comm_dtype)
        bytes_ag = 4 * sum(plan.lengths)
        # per-chip state: vector leaves are dp-sharded, scalars replicate
        state_chip = 0
        for leaf in jax.tree.leaves(self._opt_state):
            nbytes = leaf.size * leaf.dtype.itemsize
            state_chip += nbytes // dp if leaf.ndim >= 1 else nbytes
        coll_ms = gbs = overlap = None     # None = not measured
        if measure and dp > 1:
            coll_ms = self._measure_collectives(iters)
            if coll_ms > 0:
                gbs = (bytes_rs + bytes_ag) / (coll_ms / 1e3) / 1e9
            if step_ms:
                overlap = max(0.0, min(1.0, 1.0 - coll_ms / step_ms))
            _telem.set_gauge("comm.collective_ms", coll_ms)
        ov = overlap_stats or {}
        return _zero.comm_block(
            dp=dp, wire_dtype=self._comm_dtype, buckets=plan.n_buckets,
            bytes_reduced_per_step=bytes_rs,
            bytes_gathered_per_step=bytes_ag,
            grad_bytes_fp32=plan.grad_bytes_fp32(),
            collective_ms=coll_ms, est_ici_gb_s=gbs,
            overlap_efficiency=overlap, zero1=True,
            overlap_comm=self._overlap_comm,
            exposed_comm_ms=ov.get("exposed_comm_ms"),
            overlap_frac=ov.get("overlap_frac"),
            state_bytes_per_chip=state_chip, state_bytes_replicated=state_rep)

    def _measure_collectives(self, iters=10):
        """Wall-time a jitted program containing ONLY this trainer's
        per-step collectives (bucketed RS + param AG) — the measured
        ``collective_ms`` evidence for the comm block."""
        import time
        plan = self._plan
        dp = self.mesh.shape[AXIS_DP]
        mode = self._comm_dtype

        def comm_only(flats, key):
            outs = []
            for b, f in enumerate(flats):
                sh = _zero.reduce_scatter_bucket(
                    f, jax.random.fold_in(key, b), dp, mode)
                outs.append(lax.all_gather(sh, AXIS_DP, tiled=True))
            return outs

        specs = [P()] * plan.n_buckets
        f = jax.jit(shard_map(comm_only, mesh=self.mesh,
                              in_specs=(specs, P()), out_specs=specs,
                              check_vma=False))
        flats = [jnp.ones((n,), jnp.float32) for n in plan.lengths]
        key = jax.random.PRNGKey(0)
        jax.block_until_ready(f(flats, key))        # compile off the clock
        t0 = time.perf_counter()
        for _ in range(iters):
            out = f(flats, key)
        jax.block_until_ready(out)
        t1 = time.perf_counter()
        _trace.record("comm.collectives", t0, t1)
        return (t1 - t0) / iters * 1e3


def all_reduce_gradients(params, mesh=None, axis=AXIS_DP, kvstore=None,
                         keys=None):
    """Sum parameter gradients across data-parallel workers — the ONE
    implementation behind ``gluon.Trainer.allreduce_grads`` and
    standalone use (they used to be two drifting copies).

    - With ``kvstore``: one batched ``pushpull`` over all pending keys
      (the dist store coalesces into BIGARRAY_BOUND buckets — one wire
      round per bucket, not per tensor).
    - Without: a cross-*process* sum via bucketed allgather (within one
      process an eagerly computed gradient already covers the full local
      batch, so there is nothing to reduce).

    ``grad_req='add'`` accumulation is honored: a gradient is reduced
    exactly ONCE per accumulation cycle (tracked per-buffer; autograd
    writing a fresh gradient or ``zero_grad`` re-arms it), so calling
    ``allreduce_grads()`` manually and then ``step()`` — the reference's
    documented split flow — cannot double-count the cross-worker sum.
    """
    if keys is None:
        keys = list(range(len(params)))
    sel_keys, sel_params, grads = [], [], []
    for k, p in zip(keys, params):
        d = getattr(p, "_data", None)
        if getattr(p, "grad_req", "write") == "null" or d is None:
            continue
        if getattr(d, "_grad_reduced", False):
            continue            # already summed this accumulation cycle
        # a parameter no backward reached sends zeros (made at this
        # read), so every worker sends the same keys; a row_sparse one
        # has nothing to send
        g = p.grad()
        if g is None:
            continue
        sel_keys.append(k)
        sel_params.append(p)
        grads.append(g)
    if not sel_keys:
        return params
    if kvstore is not None:
        kvstore.pushpull(sel_keys, grads, out=grads)
        for p, g in zip(sel_params, grads):
            if g.stype == "row_sparse":
                # keep the compressed pair — .data here would materialize
                # a vocab-sized dense grad and disable the optimizer's
                # lazy row update
                p._data._grad = g
            else:
                p._data._grad = g.data
            p._data._grad_reduced = True
        return params
    if jax.process_count() == 1:
        return params
    from jax.experimental import multihost_utils
    from ..ndarray.sparse import RowSparseNDArray
    if any(isinstance(p._data._grad, RowSparseNDArray)
           for p in sel_params):
        raise MXNetError(
            "all_reduce_gradients: row_sparse grads need a kvstore "
            "(dist_tpu_sync row-aware path); pass kvstore=")
    garrs = [p._data._grad for p in sel_params]
    plan = _zero.BucketPlan([g.shape for g in garrs], dp=1,
                            bound_bytes=_zero.bucket_bound_bytes())
    flats = plan.flatten(garrs)
    summed = []
    for flat in flats:
        stacked = multihost_utils.process_allgather(flat)  # mxlint: disable=HB07 -- one DCN round per >=bucket-bound of payload, not per tensor
        summed.append(jnp.sum(stacked, axis=0))
    for p, g in zip(sel_params, plan.unflatten(summed, garrs)):
        p._data._grad = g
        p._data._grad_reduced = True
    return params
