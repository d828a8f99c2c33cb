"""Gluon ``Trainer`` — applies an Optimizer over a ParameterDict.

Reference: python/mxnet/gluon/trainer.py (SURVEY.md §2.2 "Gluon Trainer"):
owns the KVStore, `step(batch_size)` = allreduce_grads + update.

TPU mapping (SURVEY.md §3.2): with kvstore='tpu_sync'/'dist_tpu_sync' the
gradient allreduce is a jitted psum over the mesh data axis executed by the
KVStore facade; the optimizer update itself is a fused jax computation per
parameter (or one fused multi-tensor update via `fuse=True`).
"""
from __future__ import annotations

import os
import warnings

import jax
import jax.numpy as jnp

from .. import _tape
from ..base import MXNetError
from ..ndarray.ndarray import NDArray
from .. import optimizer as opt
from ..telemetry import tracing as _trace
from .parameter import ParameterDict, Parameter

__all__ = ["Trainer"]


def _stale(param):
    """No backward has written this parameter's gradient since the last
    update (or none reached it at all).  A gradient cleared after its
    backward (``zero_grad``) is not stale: it reads as zeros, made here,
    and a ``row_sparse`` one that was cleared has nothing to apply."""
    d = param._data
    return not d._grad_fresh or _tape.grad_array(d) is None


def _fused_adapter(optimizer):
    """(kernel_name, hyper, pack, unpack) bridging an eager Optimizer's
    state containers to the functional ``optimizer.fused_rule`` kernels,
    for the donated-jit step path; ``None`` -> optimizer not supported
    (eager per-param path runs instead).

    ``pack(i, state)`` builds the kernel-format pytree from the eager
    state WITHOUT copying (same underlying jax arrays); ``unpack(i,
    state, new_state)`` writes the kernel's outputs back into the eager
    containers so ``save_states``/``load_states`` keep working
    unchanged.
    """
    from .. import optimizer as opt_mod
    t = type(optimizer)
    if t in (opt_mod.SGD, opt_mod.NAG):
        mom = optimizer.momentum

        def pack(i, s):
            return {"mom": s.data} if mom else {}

        def unpack(i, s, ns):
            if mom:
                s._set_data(ns["mom"])
        name = "nag" if t is opt_mod.NAG else "sgd"
        return name, {"momentum": mom}, pack, unpack
    if t in (opt_mod.Adam, opt_mod.AdamW):
        def pack(i, s):
            mean, var = s
            return {"m": mean.data, "v": var.data}

        def unpack(i, s, ns):
            mean, var = s
            mean._set_data(ns["m"])
            var._set_data(ns["v"])
        name = "adamw" if t is opt_mod.AdamW else "adam"
        return (name, {"beta1": optimizer.beta1, "beta2": optimizer.beta2,
                       "epsilon": optimizer.epsilon}, pack, unpack)
    return None


def _fused_aux(optimizer):
    """Per-param host scalar the kernel needs beyond (p, g, s, lr, wd):
    Adam's bias-correction step count (eager Adam passes t-1 and the
    kernel increments — see Adam.update).  Shipped stacked in ONE device
    vector and injected as state key ``aux_key`` inside the trace."""
    from .. import optimizer as opt_mod
    if type(optimizer) in (opt_mod.Adam, opt_mod.AdamW):
        return "t", lambda i: optimizer._index_update_count[i] - 1
    return None, None


def _state_shape_ok(optimizer, state):
    """Phase-1 sanity check that an EXISTING eager state matches what the
    adapter's pack() expects (a loaded/custom state in another layout
    falls back to the exact eager path instead of crashing)."""
    from .. import optimizer as opt_mod
    t = type(optimizer)
    if t in (opt_mod.SGD, opt_mod.NAG):
        return (state is None) == (optimizer.momentum == 0.0) and \
            (state is None or isinstance(state, NDArray))
    if t in (opt_mod.Adam, opt_mod.AdamW):
        return isinstance(state, tuple) and len(state) == 2 and \
            all(isinstance(x, NDArray) for x in state)
    return False


class Trainer:
    def __init__(self, params, optimizer, optimizer_params=None, kvstore="device",
                 compression_params=None, update_on_kvstore=None):
        if isinstance(params, (dict, ParameterDict)):
            params = [params[key] for key in sorted(list(params.keys()))]
        if not isinstance(params, (list, tuple)):
            raise MXNetError(
                "First argument must be a list or dict of Parameters, "
                f"got {type(params)}.")
        self._params = []
        self._param2idx = {}
        for i, param in enumerate(params):
            if not isinstance(param, Parameter):
                raise MXNetError(
                    "First argument must be a list or dict of Parameters, "
                    f"got list of {type(param)}.")
            self._param2idx[param.name] = i
            self._params.append(param)
        self._compression_params = compression_params
        optimizer_params = optimizer_params or {}
        self._scale = float(optimizer_params.get("rescale_grad", 1.0))
        self._init_optimizer(optimizer, optimizer_params)
        self._kvstore_type = kvstore
        self._kvstore = None
        self._kv_initialized = False
        self._states = {}
        self._update_on_kvstore = update_on_kvstore
        self._fused_jit_cache = {}
        # backward-overlapped gradient communication (ISSUE 5): an
        # OverlapScheduler dispatching per-bucket kvstore rounds from
        # autograd grad-ready hooks; armed in _init_kvstore when the
        # store actually spans workers (MXTPU_OVERLAP_COMM=0 kills it)
        self._overlap = None

    def _init_optimizer(self, optimizer, optimizer_params):
        param_dict = {i: param for i, param in enumerate(self._params)}
        if isinstance(optimizer, opt.Optimizer):
            if optimizer_params and set(optimizer_params) != {"rescale_grad"}:
                raise MXNetError(
                    "optimizer_params must be None if optimizer is an "
                    "Optimizer instance")
            self._optimizer = optimizer
            self._optimizer.param_dict = param_dict
        else:
            self._optimizer = opt.create(optimizer, param_dict=param_dict,
                                         **optimizer_params)

    def _init_kvstore(self):
        from .. import kvstore as kvs
        if self._kvstore_type is None or self._kvstore_type is False:
            self._kvstore = None
        elif isinstance(self._kvstore_type, str):
            self._kvstore = kvs.create(self._kvstore_type)
        else:
            self._kvstore = self._kvstore_type
        if self._kvstore is not None:
            for i, p in enumerate(self._params):
                if p._data is not None and p.grad_req != "null":
                    self._kvstore.init(i, p.data())
        from ..parallel import zero as _zero
        if self._kvstore is not None and \
                getattr(self._kvstore, "num_workers", 1) > 1 and \
                _zero.overlap_comm_enabled():
            from ..parallel.overlap import OverlapScheduler
            self._overlap = OverlapScheduler(
                self._params, kvstore=self._kvstore).install()
        self._kv_initialized = True

    @property
    def learning_rate(self):
        return self._optimizer.learning_rate

    @property
    def optimizer(self):
        return self._optimizer

    def set_learning_rate(self, lr):
        self._optimizer.set_learning_rate(lr)

    def _all_reduce_grads(self):
        reduces = not (self._kvstore is None or
                       self._kvstore.num_workers <= 1 and
                       type(self._kvstore).__name__ == "KVStoreLocal")
        if self._overlap is None and not reduces:
            return
        with _trace.span("gluon.update.allreduce"):
            if self._overlap is not None:
                # buckets whose grads finished during backward already
                # went out (async); this launches stragglers and waits
                # ONLY on the tail bucket.  Reduced grads carry
                # _grad_reduced, so the batched pass below cannot
                # double-count them.
                self._overlap.finish()
            if reduces:
                # ONE implementation shared with
                # parallel.all_reduce_gradients (they used to be drifting
                # copies): one batched pushpull, the dist store coalesces
                # into BIGARRAY_BOUND buckets, and each accumulated
                # gradient (grad_req='add') is reduced exactly once per
                # cycle — allreduce_grads() then step() can't double-count.
                from ..parallel.data_parallel import all_reduce_gradients
                all_reduce_gradients(self._params, kvstore=self._kvstore)

    def allreduce_grads(self):
        if not self._kv_initialized:
            self._init_kvstore()
        self._all_reduce_grads()

    def step(self, batch_size, ignore_stale_grad=False):
        """rescale grads by 1/batch_size, allreduce, update.
        Reference: Trainer.step."""
        if not self._kv_initialized:
            self._init_kvstore()
        self._optimizer.rescale_grad = self._scale / batch_size
        with _trace.span("gluon.update") as sp:
            self._all_reduce_grads()
            self._update(ignore_stale_grad, sp)

    def update(self, batch_size, ignore_stale_grad=False):
        """update only (user did allreduce manually)."""
        if not self._kv_initialized:
            self._init_kvstore()
        self._optimizer.rescale_grad = self._scale / batch_size
        with _trace.span("gluon.update") as sp:
            self._update(ignore_stale_grad, sp)

    def _sharded_update_mesh(self):
        """Ambient dp mesh for weight-update sharding of the fused step
        (arXiv:1909.09756 — MLPerf's TPU-pod trick): when training under
        ``mesh_scope`` with a dp axis, the group update computes each
        eligible parameter's new value on a 1/N shard per chip (with the
        optimizer state living sharded) and all-gathers the result.
        ``MXTPU_SHARDED_SYNC=0`` kills it; no mesh -> exact old path."""
        from ..parallel.mesh import current_mesh, AXIS_DP
        from ..parallel import zero as _zero
        mesh = current_mesh()
        if mesh is None or AXIS_DP not in mesh.axis_names or \
                mesh.shape[AXIS_DP] <= 1 or not _zero.sharded_sync_enabled():
            return None
        return mesh

    def _get_fused_jit(self, apply_fn, aux_key, key, mesh=None):
        """ONE donated XLA program updating the whole parameter group:
        old params and optimizer state are donated (buffers reused for
        the outputs — no per-step param copy), and XLA fuses the N
        elementwise update chains into one launch.  lr/wd/aux/rescale
        enter as device arrays so hyperparameter and step-count changes
        never retrace.  With ``mesh`` (see :meth:`_sharded_update_mesh`)
        the per-param update is sharded over 'dp' — XLA lowers the
        grad feed into a slice per chip and all-gathers the fresh
        params, the eager-trainer half of the ZeRO-1 pipeline."""
        jitted = self._fused_jit_cache.get(key)
        if jitted is None:
            if mesh is not None:
                from jax.sharding import NamedSharding, PartitionSpec as P
                from ..parallel.mesh import AXIS_DP
                dp = mesh.shape[AXIS_DP]

                def ws_spec(ndim):
                    return NamedSharding(
                        mesh, P(*([AXIS_DP] + [None] * (ndim - 1))))

                def shardable(x):
                    return getattr(x, "ndim", 0) >= 1 and \
                        x.shape[0] % dp == 0 and x.shape[0] >= dp

            def group_update(params, grads, states, lr_vec, wd_vec,
                             aux_vec, rescale):
                # lr/wd/aux arrive stacked in ONE device array each (one
                # H2D per step however many params there are); the
                # per-param slice is a traced op inside the program
                new_ps, new_ss = [], []
                for j, (p, g, s) in enumerate(zip(params, grads,
                                                  states)):
                    g = g * rescale.astype(g.dtype)
                    if aux_key is not None:
                        s = dict(s)
                        s[aux_key] = aux_vec[j]
                    sharded = mesh is not None and shardable(p)
                    if sharded:
                        p = jax.lax.with_sharding_constraint(
                            p, ws_spec(p.ndim))
                        g = jax.lax.with_sharding_constraint(
                            g, ws_spec(g.ndim))
                        s = {k: jax.lax.with_sharding_constraint(
                                v, ws_spec(v.ndim)) if shardable(v) else v
                             for k, v in s.items()}
                    # scalars cast to the param dtype: the eager path's
                    # python floats promote WEAKLY (bf16 params stay
                    # bf16); strong f32 scalars would widen them
                    np_, ns = apply_fn(p, g, s,
                                       lr_vec[j].astype(p.dtype),
                                       wd_vec[j].astype(p.dtype))
                    if sharded:
                        # all-gather the fresh params; state STAYS
                        # sharded across steps (1/N optimizer HBM)
                        np_ = jax.lax.with_sharding_constraint(
                            np_, NamedSharding(
                                mesh, P(*([None] * np_.ndim))))
                    new_ps.append(np_)
                    new_ss.append(ns)
                return new_ps, new_ss
            jitted = jax.jit(group_update, donate_argnums=(0, 2))
            self._fused_jit_cache[key] = jitted
        return jitted

    def _get_flat_fused_jit(self, name, hyper, clip, aux_key, key):
        """ONE flat-bucket program for the whole parameter group
        (ISSUE 6: the reference's multi_sgd-style multi-tensor update):
        params/grads/state concatenate into single flat f32 views and
        the update runs ONCE over the bucket — on TPU as a single Pallas
        kernel (ops/fused_update.py), elsewhere as one fused XLA chain
        instead of one chain per parameter.  Elementwise math is
        IDENTICAL to the per-param path (same kernel functions over the
        same values), so results are bitwise-equal; qualification
        happens host-side in _fused_jit_update."""
        jitted = self._fused_jit_cache.get(key)
        if jitted is None:
            from ..ops.fused_update import fused_bucket_rule
            _, bucket_apply = fused_bucket_rule(name, clip_gradient=clip,
                                                **hyper)

            def group_update_flat(params, grads, states, lr, wd, aux,
                                  rescale):
                shapes = [p.shape for p in params]
                sizes = [p.size for p in params]
                flat_p = jnp.concatenate([jnp.ravel(p) for p in params])
                flat_g = jnp.concatenate([jnp.ravel(g) for g in grads]) \
                    * rescale
                state = {leaf: jnp.concatenate(
                    [jnp.ravel(s[leaf]) for s in states])
                    for leaf in states[0]}
                if aux_key is not None:
                    state[aux_key] = aux
                new_flat, new_state = bucket_apply(flat_p, flat_g, state,
                                                   lr, wd)
                new_ps, new_ss = [], []
                off = 0
                for sh, n in zip(shapes, sizes):
                    new_ps.append(new_flat[off:off + n].reshape(sh))
                    # vector leaves slice back per param; scalar leaves
                    # (adam's t) are aux-managed and unpack ignores them
                    new_ss.append({
                        leaf: v[off:off + n].reshape(sh)
                        for leaf, v in new_state.items()
                        if getattr(v, "ndim", 0) >= 1})
                    off += n
                return new_ps, new_ss

            jitted = jax.jit(group_update_flat, donate_argnums=(0, 2))
            self._fused_jit_cache[key] = jitted
        return jitted

    def _fused_jit_update(self, ignore_stale_grad):
        """Fused, jitted, donated update for the whole parameter group
        (the Trainer-side half of the overlapped-pipeline tentpole; the
        fully fused fwd/bwd/update lives in parallel.DataParallelTrainer).
        Returns the number of parameters it updated, or False where it
        falls back: optimizers without a functional kernel,
        sparse/accumulating grads, multi-precision, or unexpected loaded
        state layouts — the exact eager path then runs.  Disable with
        MXTPU_FUSED_STEP=0.

        When the whole group is uniform (same lr/wd/step count, all f32,
        a flat-able rule) the group collapses further into ONE
        flat-bucket update via :meth:`_get_flat_fused_jit`
        (``MXTPU_FUSED_STEP_FLAT=0`` kills that layer only)."""
        from ..ndarray import sparse as _sp
        optimizer = self._optimizer
        if os.environ.get("MXTPU_FUSED_STEP", "1") == "0" or \
                optimizer.multi_precision:
            return False
        adapter = _fused_adapter(optimizer)
        if adapter is None:
            return False
        name, hyper, pack, unpack = adapter
        # phase 1: qualification only — nothing is mutated, so bailing
        # to the per-param path cannot double-count updates
        idxs, params = [], []
        for i, param in enumerate(self._params):
            if param.grad_req == "null" or param._data is None:
                continue
            if _stale(param):
                if ignore_stale_grad:
                    continue
                return False      # per-param path raises the right error
            if param.grad_req == "add" or \
                    isinstance(param._data._grad, _sp.RowSparseNDArray):
                return False      # sparse/accumulating grads: exact path
            if i in self._states and \
                    not _state_shape_ok(optimizer, self._states[i]):
                return False      # foreign state layout: exact path
            idxs.append(i)
            params.append(param)
        if not idxs:
            return 0
        # phase 2: commit — counters/lr/wd evaluated once per param
        # (identical bookkeeping to the eager loop), then one jit call
        for i in idxs:
            optimizer._update_count(i)
            if i not in self._states:
                self._states[i] = optimizer.create_state_multi_precision(
                    i, self._params[i].data())
        lrs = [optimizer._get_lr(i) for i in idxs]
        wds = [optimizer._get_wd(i) for i in idxs]
        lr_vec = jnp.asarray(lrs, jnp.float32)
        wd_vec = jnp.asarray(wds, jnp.float32)
        aux_key, aux_fn = _fused_aux(optimizer)
        auxs = [aux_fn(i) for i in idxs] if aux_fn else [0] * len(idxs)
        aux_vec = jnp.asarray(auxs, jnp.int32)
        pvals = [p._data._data for p in params]
        gvals = [p._data._grad for p in params]
        svals = [pack(i, self._states[i]) for i in idxs]
        mesh = self._sharded_update_mesh()
        # flat-bucket qualification (host-side: lr/wd/aux VALUES are
        # known here): a uniform all-f32 group collapses into one
        # flat update — bitwise the same math, one kernel walk
        flat = (mesh is None and len(idxs) > 1
                and os.environ.get("MXTPU_FUSED_STEP_FLAT", "1") != "0"
                and name in ("sgd", "nag", "adam", "adamw")
                and len(set(map(float, lrs))) == 1
                and len(set(map(float, wds))) == 1
                and len(set(map(int, auxs))) == 1
                and all(v.dtype == jnp.float32 for v in pvals)
                and all(g.dtype == jnp.float32 for g in gvals))
        if mesh is not None:
            # values committed off-mesh (fresh eager backward grads,
            # first-step params/state) conflict with the in-program
            # sharding constraints; re-place them replicated on the
            # mesh.  Leaves already living on the mesh — params and the
            # dp-sharded state after step 1 — pass through untouched, so
            # the steady state pays one device_put for the grads only.
            from jax.sharding import NamedSharding, PartitionSpec as _P
            rep = NamedSharding(mesh, _P())

            def _place(x):
                sh = getattr(x, "sharding", None)
                if isinstance(sh, NamedSharding) and sh.mesh == mesh:
                    return x
                return jax.device_put(x, rep)

            orig_shardings = [v.sharding for v in pvals]
            pvals = [_place(v) for v in pvals]
            gvals = [_place(v) for v in gvals]
            svals = [{k: _place(v) for k, v in s.items()} for s in svals]
        key = (name, tuple(sorted(hyper.items())),
               optimizer.clip_gradient, aux_key,
               tuple((v.shape, str(v.dtype)) for v in pvals),
               tuple(tuple(sorted(s)) for s in svals),
               None if mesh is None else tuple(sorted(mesh.shape.items())),
               "flat" if flat else "per-param")
        rescale = jnp.asarray(optimizer.rescale_grad, jnp.float32)
        with warnings.catch_warnings():
            # donation is a TPU/GPU optimization; CPU ignores it with a
            # UserWarning that would spam every step
            warnings.filterwarnings("ignore", message=".*[Dd]onat")
            if flat:
                jitted = self._get_flat_fused_jit(
                    name, hyper, optimizer.clip_gradient, aux_key, key)
                new_ps, new_ss = jitted(
                    pvals, gvals, svals,
                    jnp.asarray(lrs[0], jnp.float32),
                    jnp.asarray(wds[0], jnp.float32),
                    jnp.asarray(auxs[0], jnp.int32), rescale)
            else:
                _, apply_fn = opt.fused_rule(
                    name, clip_gradient=optimizer.clip_gradient, **hyper)
                jitted = self._get_fused_jit(apply_fn, aux_key, key,
                                             mesh=mesh)
                try:
                    new_ps, new_ss = jitted(pvals, gvals, svals, lr_vec,
                                            wd_vec, aux_vec, rescale)
                except Exception:  # noqa: BLE001 — sharded lowering can
                    # fail (e.g. values committed to an incompatible
                    # device set); the replicated program is always
                    # valid. Lowering failures happen before buffers are
                    # donated.
                    if mesh is None:
                        raise
                    jitted = self._get_fused_jit(apply_fn, aux_key,
                                                 key + ("replicated",))
                    new_ps, new_ss = jitted(pvals, gvals, svals, lr_vec,
                                            wd_vec, aux_vec, rescale)
        if mesh is not None:
            # fresh params return to their pre-update placement so the
            # next eager forward never mixes device sets; only the
            # optimizer state stays resident on the mesh (the 1/N HBM
            # saving lives there, and it re-enters the next update
            # without a transfer)
            new_ps = [jax.device_put(v, sh)
                      for v, sh in zip(new_ps, orig_shardings)]
        for i, param, np_, ns in zip(idxs, params, new_ps, new_ss):
            param._data._set_data(np_)
            unpack(i, self._states[i], ns)
            param._data._grad_fresh = False
        return len(idxs)

    def _fused_group_update(self, ignore_stale_grad):
        """ONE multi-tensor op for the whole parameter group (reference
        multi_sgd_mom_update, src/operator/optimizer_op.cc): collapses N
        eager dispatches per step into one XLA program. Only the plain
        dense-SGD case qualifies; anything else falls back per-param
        (False; else the number of parameters updated)."""
        from .. import optimizer as opt_mod
        from ..ndarray import sparse as _sp
        from ..ndarray import ops as _ops
        opt = self._optimizer
        if type(opt) is not opt_mod.SGD or opt.multi_precision:
            return False
        # phase 1: qualification only — no optimizer state is touched, so
        # bailing to the per-param path cannot double-count updates
        arrays, idxs = [], []
        for i, param in enumerate(self._params):
            if param.grad_req == "null" or param._data is None:
                continue
            if _stale(param):
                if ignore_stale_grad:
                    continue
                return False      # per-param path raises the right error
            if param.grad_req == "add" or \
                    isinstance(param._data._grad, _sp.RowSparseNDArray):
                return False      # sparse/accumulating grads: exact path
            idxs.append(i)
            arrays.append((param, param.data(), param.grad()))
        if not arrays:
            return 0
        # phase 2: commit — counters/lr/wd evaluated once per param
        lrs, wds = [], []
        for i in idxs:
            opt._update_count(i)
            lrs.append(opt._get_lr(i))
            wds.append(opt._get_wd(i))
        if opt.momentum:
            flat = []
            for i, (param, w, g) in zip(idxs, arrays):
                if i not in self._states:
                    self._states[i] = opt.create_state_multi_precision(
                        i, w)
                flat += [w, g, self._states[i]]
            _ops.multi_sgd_mom_update(
                *flat, lrs=lrs, wds=wds, momentum=opt.momentum,
                rescale_grad=opt.rescale_grad,
                clip_gradient=opt.clip_gradient)
        else:
            flat = []
            for param, w, g in arrays:
                flat += [w, g]
            _ops.multi_sgd_update(
                *flat, lrs=lrs, wds=wds, rescale_grad=opt.rescale_grad,
                clip_gradient=opt.clip_gradient)
        for param, _, _ in arrays:
            param._data._grad_fresh = False
        return len(arrays)

    def _update(self, ignore_stale_grad=False, sp=None):
        """One of three paths, named on the ``gluon.update`` span ``sp``
        with the jitted calls it dispatched (``programs``: one for a
        fused path however many parameters, one optimizer call per
        parameter on the eager path, each of them several un-jitted
        operations) and the parameters it updated."""
        for path, fused in (("fused_jit", self._fused_jit_update),
                            ("fused_group", self._fused_group_update)):
            done = fused(ignore_stale_grad)
            if done is not False:
                _trace.annotate(sp, path=path, programs=min(done, 1),
                                params=done)
                return
        updated = 0
        for i, param in enumerate(self._params):
            if param.grad_req == "null" or param._data is None:
                continue
            if _stale(param):
                if ignore_stale_grad:
                    continue
                raise MXNetError(
                    f"Gradient of Parameter `{param.name}` has not been "
                    "computed. Call backward first, or set grad_req to "
                    "'null' / use ignore_stale_grad=True.")
            if i not in self._states:
                self._states[i] = self._optimizer.create_state_multi_precision(
                    i, param.data())
            self._optimizer.update_multi_precision(
                i, param.data(), param.grad(), self._states[i])
            param._data._grad_fresh = False
            updated += 1
            if param.grad_req == "add":
                param.zero_grad()
        _trace.annotate(sp, path="eager", programs=updated, params=updated)

    # -- checkpoint protocol (mx.checkpoint.CheckpointManager) ----------
    def _counters(self):
        return {
            "num_update": self._optimizer.num_update,
            "begin_num_update": self._optimizer.begin_num_update,
            "index_update_count": dict(self._optimizer._index_update_count),
        }

    def _set_counters(self, counters):
        self._optimizer.num_update = counters.get("num_update", 0)
        self._optimizer.begin_num_update = counters.get(
            "begin_num_update", 0)
        self._optimizer._index_update_count = {
            int(k): v for k, v
            in counters.get("index_update_count", {}).items()}

    @staticmethod
    def _encode_state(s, key, arrays):
        """JSON-able layout descriptor + flat array dict for one param's
        optimizer state (NDArray leaves, arbitrarily nested tuples —
        multi-precision states nest (inner, master))."""
        if s is None:
            return None
        if isinstance(s, NDArray):
            arrays[key] = s
            return "nd"
        if isinstance(s, tuple):
            return ["tuple", [Trainer._encode_state(x, f"{key}.{j}", arrays)
                              for j, x in enumerate(s)]]
        raise MXNetError(
            f"cannot checkpoint optimizer state leaf of type {type(s)}")

    @staticmethod
    def _decode_state(desc, key, arrays):
        if desc is None:
            return None
        if desc == "nd":
            return arrays[key]
        kind, items = desc
        if kind == "tuple":
            return tuple(Trainer._decode_state(d, f"{key}.{j}", arrays)
                         for j, d in enumerate(items))
        raise MXNetError(f"unknown optimizer state descriptor {desc!r}")

    def state_dict(self):
        """Full trainer state as ``{"arrays": {name: NDArray}, "meta":
        json-able}`` — the CheckpointManager protocol.  Arrays are
        host-materializable whatever their device placement (the
        shard_updates mesh-resident state gathers on D2H), so the saved
        form is dp-independent."""
        arrays = {}
        layout = {}
        for i, s in self._states.items():
            layout[str(i)] = self._encode_state(s, f"opt/{i}", arrays)
        meta = {"kind": "gluon.Trainer",
                "optimizer": type(self._optimizer).__name__,
                "layout": layout, "counters": self._counters()}
        return {"arrays": arrays, "meta": meta}

    def load_state_dict(self, d):
        """Inverse of :meth:`state_dict` onto this (possibly fresh)
        trainer; the fused/sharded update paths re-place restored host
        arrays onto the mesh on their next step."""
        arrays, meta = d["arrays"], d["meta"]
        states = {}
        for k, desc in meta.get("layout", {}).items():
            states[int(k)] = self._decode_state(desc, f"opt/{k}", arrays)
        self._states = states
        self._set_counters(meta.get("counters", {}))

    def save_states(self, fname):
        """Reference: Trainer.save_states (optimizer state incl. update
        counts — Adam/LAMB bias correction and lr schedules depend on them)."""
        import pickle
        updater = opt.Updater(self._optimizer)
        updater.states = dict(self._states)
        counters = {
            "num_update": self._optimizer.num_update,
            "begin_num_update": self._optimizer.begin_num_update,
            "index_update_count": dict(self._optimizer._index_update_count),
        }
        with open(fname, "wb") as f:
            f.write(pickle.dumps({"states": updater.get_states(),
                                  "counters": counters}))

    def load_states(self, fname):
        import pickle
        with open(fname, "rb") as f:
            blob = f.read()
        try:
            payload = pickle.loads(blob)
        except Exception:
            payload = None
        updater = opt.Updater(self._optimizer)
        if isinstance(payload, dict) and "states" in payload:
            updater.set_states(payload["states"])
            counters = payload.get("counters", {})
            self._optimizer.num_update = counters.get("num_update", 0)
            self._optimizer.begin_num_update = counters.get(
                "begin_num_update", 0)
            self._optimizer._index_update_count = dict(
                counters.get("index_update_count", {}))
        else:  # legacy blob: raw updater states
            updater.set_states(blob)
        self._states = dict(updater.states)
