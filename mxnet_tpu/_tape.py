"""Imperative autograd tape.

TPU-native replacement for the reference's C++ imperative autograd runtime
(``src/imperative/imperative.cc``: ``Imperative::RecordOp`` /
``Imperative::Backward``; SURVEY.md §2.1 "Imperative runtime + autograd").

Design (SURVEY.md §7 "core trick"): JAX's autodiff is functional, while MXNet's
API is an imperative tape (``autograd.record()`` … ``loss.backward()``). We
bridge them by recording, at dispatch time, one tape *node* per executed op.
While recording, every op is executed through ``jax.vjp`` so the node captures
a ready-to-run pullback (residuals live on device — this IS the forward pass,
nothing is computed twice). ``backward()`` then walks nodes in reverse creation
order, feeding output cotangents into each pullback and accumulating input
cotangents into either producer nodes or user gradients (``attach_grad`` with
``grad_req`` write/add/null, matching ``Imperative::MarkVariables``).
"""
from __future__ import annotations

import threading

import jax
import jax.numpy as jnp

from .base import MXNetError
from .telemetry import tracing as _trace

__all__ = ["is_recording", "is_training", "set_recording", "set_training",
           "apply_op", "backward", "mark_variable", "grad_array",
           "grad_bytes", "clear_grad", "Node", "register_grad_ready_hook"]


class _TapeState(threading.local):
    def __init__(self):
        self.recording = False
        self.training = False
        self.counter = 0
        # inside a jit trace we must not record (pure replay), see CachedOp
        self.trace_depth = 0
        # autograd.grad() temporarily hijacks _grad/_grad_req on its
        # variables; grad-ready hooks must not observe that scratch state
        self.hooks_disabled = False


_STATE = _TapeState()


def is_recording():
    return _STATE.recording and _STATE.trace_depth == 0


def is_training():
    return _STATE.training


def set_recording(flag):
    prev = _STATE.recording
    _STATE.recording = flag
    return prev


def set_training(flag):
    prev = _STATE.training
    _STATE.training = flag
    return prev


class trace_scope:
    """Disable tape recording while tracing a CachedOp/jit region."""

    def __enter__(self):
        _STATE.trace_depth += 1
        return self

    def __exit__(self, *exc):
        _STATE.trace_depth -= 1
        return False


class Node:
    """One recorded op: inputs, pullback, and per-output cotangent slots."""

    __slots__ = ("inputs", "vjp_fn", "fn", "n_out", "out_grads",
                 "out_protos", "order", "name", "__weakref__")

    def __init__(self, inputs, vjp_fn, outs, order, name="", fn=None):
        self.inputs = inputs            # list[NDArray]
        self.vjp_fn = vjp_fn
        self.fn = fn                    # pure forward, kept for replay
        self.n_out = len(outs)
        self.out_grads = [None] * self.n_out
        self.out_protos = [(o.shape, o.dtype) for o in outs]
        self.order = order
        self.name = name


class SparseCotangent:
    """A row-sparse cotangent flowing through backward: (row indices,
    row values, dense shape). Produced by ops with ``sparse_grad=True``
    (Embedding); accumulated leaf-side without densifying — the memory
    contract of reference row_sparse gradients (SURVEY.md §2.5)."""

    __slots__ = ("indices", "values", "shape")

    def __init__(self, indices, values, shape):
        self.indices = indices   # jnp int array (rows,)
        self.values = values     # jnp array (rows, ...)
        self.shape = tuple(shape)

    @property
    def dtype(self):
        return self.values.dtype

    def densify(self):
        # .add, not .set: indices may repeat (Embedding emits raw batch
        # ids) and duplicate rows must SUM
        return jnp.zeros(self.shape, self.values.dtype) \
            .at[self.indices].add(self.values)

    def merge(self, other):
        """Sum with another sparse cotangent of the same dense shape —
        indices concat now, dedup deferred to materialization."""
        return SparseCotangent(
            jnp.concatenate([self.indices, other.indices]),
            jnp.concatenate([self.values, other.values], axis=0),
            self.shape)

    def dedup(self):
        from .ndarray.sparse import sum_duplicate_rows
        uniq, summed = sum_duplicate_rows(self.indices, self.values)
        return SparseCotangent(uniq, summed, self.shape)

    def astype(self, dtype):
        return SparseCotangent(self.indices, self.values.astype(dtype),
                               self.shape)


def _add_cotangents(a, b):
    """Sum two cotangents, either of which may be sparse."""
    a_sp = isinstance(a, SparseCotangent)
    b_sp = isinstance(b, SparseCotangent)
    if a_sp and b_sp:
        return a.merge(b)
    if a_sp:
        return b.at[a.indices].add(a.values)
    if b_sp:
        return a.at[b.indices].add(b.values)
    return a + b


def _on_tape(arr):
    return arr._grad_req != "null" or arr._node is not None


def apply_op(fn, inputs, n_out=1, name=""):
    """Execute ``fn`` (pure, jax arrays -> jax array(s)) over NDArray inputs.

    Every NDArray op routes through here — the single dispatch point standing
    in for ``Imperative::Invoke`` (reference src/imperative/imperative.cc).
    Returns raw jax output(s) plus the Node to attach (or None).
    """
    datas = [x._data for x in inputs]
    record = is_recording() and any(_on_tape(x) for x in inputs)
    try:
        if record:
            outs, vjp_fn = jax.vjp(lambda *a: fn(*a), *datas)
            if n_out == 1:
                outs = (outs,)
            _STATE.counter += 1
            node = Node(list(inputs), vjp_fn, outs, _STATE.counter, name,
                        fn=fn)
            return outs, node
        outs = fn(*datas)
        if n_out == 1:
            outs = (outs,)
        return outs, None
    except FloatingPointError as e:
        # MXTPU_DEBUG_NANS=1: jax_debug_nans raised on the first NaN/Inf —
        # attach the framework op name (jax only names the XLA primitive).
        # If the user enabled jax debug_nans themselves, leave the exception
        # type alone so their `except FloatingPointError` handlers still work.
        from . import debug as _debug
        if not _debug.debug_nans_enabled():
            raise
        raise MXNetError(
            f"NaN/Inf produced by op '{name or getattr(fn, '__name__', fn)}'"
            f" (MXTPU_DEBUG_NANS): {e}") from e


def mark_variable(arr, grad_req="write", stype=None):
    """attach_grad: reference Imperative::MarkVariables.

    Records ``grad_req`` and the gradient's storage type and holds no
    array: a gradient array exists once a backward has produced one or
    something has asked for one (:func:`grad_array`).  A fused step, a
    served network and every variable no backward reaches never do, and
    carry no buffer of the variable's size."""
    if grad_req not in ("write", "add", "null"):
        raise MXNetError(f"invalid grad_req {grad_req!r}")
    arr._grad_req = grad_req
    arr._grad_stype = stype
    # attach_grad detaches the array from any producing graph, matching the
    # reference behaviour of NDArray.attach_grad (python/mxnet/ndarray/ndarray.py)
    arr._node = None
    arr._out_index = 0
    arr._grad = None
    arr._grad_fresh = False


def grad_array(arr):
    """The variable's gradient array.  A dense variable that takes
    gradients and has none yet gets zeros here, made at this read and
    kept (what the reference shows in ``x.grad`` before a backward, and
    the array a later in-place clip writes through).  ``None`` for
    ``grad_req='null'`` and for a ``row_sparse`` gradient no backward has
    installed (O(nnz) memory, never a dense zero buffer)."""
    if arr._grad is None and arr._grad_req != "null" and \
            arr._grad_stype != "row_sparse":
        arr._grad = jnp.zeros(arr.shape, arr.dtype)
    return arr._grad


def grad_bytes(arr):
    """Device bytes of the gradient array ``arr`` holds now: 0 in the
    empty state, O(nnz) for a row_sparse one."""
    g = arr._grad
    if g is None:
        return 0
    handles = g._sync_handles() if hasattr(g, "_sync_handles") else (g,)
    return sum(h.nbytes for h in handles)


def clear_grad(arr):
    """zero_grad: back to the empty state, which reads as zeros and from
    which ``grad_req='add'`` accumulates."""
    arr._grad = None
    arr._grad_reduced = False   # new accumulation cycle


def _accumulate(slot, value):
    return value if slot is None else slot + value


# ---------------------------------------------------------------------------
# grad-ready hooks (ISSUE 5 tentpole): fire per variable, in backward order,
# the moment its gradient is FINAL — no remaining tape node can still
# contribute.  parallel.OverlapScheduler hangs per-bucket gradient
# communication off these so collectives overlap the rest of backprop
# instead of waiting for the whole backward (arXiv:2011.03641 §4).
# ---------------------------------------------------------------------------

_HOOK_COUNTER = [0]


class _HookHandle:
    """Returned by :func:`register_grad_ready_hook`; ``remove()``
    unregisters."""

    __slots__ = ("_arr", "_key")

    def __init__(self, arr, key):
        self._arr = arr
        self._key = key

    def remove(self):
        hooks = getattr(self._arr, "_grad_hooks", None)
        if hooks:
            hooks.pop(self._key, None)


def register_grad_ready_hook(arr, fn):
    """Register ``fn(arr)`` to run when ``arr``'s gradient is finalized
    by a backward pass (after grad_req write/add is applied, so
    ``arr._grad`` holds the finished value).  Hooks fire in backward
    order — variables used late in the forward fire first.  Returns a
    handle with ``remove()``."""
    if arr._grad_hooks is None:
        arr._grad_hooks = {}
    _HOOK_COUNTER[0] += 1
    key = _HOOK_COUNTER[0]
    arr._grad_hooks[key] = fn
    return _HookHandle(arr, key)


def _finalize_leaf(arr, g):
    """Apply grad_req and fire the variable's grad-ready hooks."""
    _apply_grad_req(arr, g)
    hooks = arr._grad_hooks
    if hooks and not _STATE.hooks_disabled:
        for fn in list(hooks.values()):
            fn(arr)


class suppress_grad_hooks:
    """Scope that keeps grad-ready hooks from firing (autograd.grad)."""

    def __enter__(self):
        self._prev = _STATE.hooks_disabled
        _STATE.hooks_disabled = True
        return self

    def __exit__(self, *exc):
        _STATE.hooks_disabled = self._prev
        return False


def backward(heads, head_grads=None, retain_graph=False, train_mode=True):
    """Run the reverse pass from ``heads``.

    Reference: ``Imperative::Backward`` (src/imperative/imperative.cc) invoked
    from ``python/mxnet/autograd.py`` ``backward()``.  One
    ``autograd.backward`` span covers the walk (``nodes``: tape nodes
    reached from the heads; ``heads``).
    """
    if not isinstance(heads, (list, tuple)):
        heads = [heads]
    with _trace.span("autograd.backward", heads=len(heads)) as sp:
        nodes = _backward(heads, head_grads, retain_graph)
        _trace.annotate(sp, nodes=nodes)


def _backward(heads, head_grads, retain_graph):
    """The walk itself; returns the number of tape nodes it reached."""
    if head_grads is None:
        head_grads = [None] * len(heads)
    elif not isinstance(head_grads, (list, tuple)):
        head_grads = [head_grads]

    # Per-backward leaf accumulator: within ONE backward pass contributions
    # always sum; grad_req write/add governs behaviour ACROSS backward calls
    # (matching reference grad_req semantics in include/mxnet/op_attr_types.h).
    leaf_grads = {}

    def _leaf_accumulate(arr, g):
        if id(arr) in leaf_grads:
            leaf_grads[id(arr)] = (arr, _add_cotangents(
                leaf_grads[id(arr)][1], g))
        else:
            leaf_grads[id(arr)] = (arr, g)

    # seed output cotangents
    live = False
    for h, hg in zip(heads, head_grads):
        seed = jnp.ones(h.shape, h.dtype) if hg is None else hg._data
        if h._node is not None and h._node.vjp_fn is not None:
            node, idx = h._node, h._out_index
            node.out_grads[idx] = _accumulate(node.out_grads[idx], seed)
            live = True
        elif h._grad_req != "null":
            _leaf_accumulate(h, seed)

    if not live:
        for arr, g in leaf_grads.values():
            _finalize_leaf(arr, g)
        return 0

    # Collect the subgraph reachable from the heads (the tape holds no
    # global node list: the graph lives in NDArray._node / Node.inputs
    # references, so dropped graphs are garbage-collected and backward on
    # one graph can never disturb another recorded in the same scope).
    reachable = {}
    stack = [h._node for h in heads
             if h._node is not None and h._node.vjp_fn is not None]
    while stack:
        node = stack.pop()
        if id(node) in reachable:
            continue
        reachable[id(node)] = node
        for inp in node.inputs:
            if inp._node is not None and inp._node.vjp_fn is not None:
                stack.append(inp._node)

    # Per-leaf pending contribution counts: a grad-capable leaf is FINAL
    # (ready to fire its hooks) once every reachable node that lists it
    # as an input has been visited by the walk below.  Counted per input
    # POSITION, matching the zip(node.inputs, in_grads) delivery loop.
    pending = {}
    for node in reachable.values():
        for inp in node.inputs:
            if inp._grad_req != "null":
                pending[id(inp)] = pending.get(id(inp), 0) + 1

    def _maybe_finalize(arr):
        if pending.get(id(arr), 0) == 0 and id(arr) in leaf_grads:
            a, g = leaf_grads.pop(id(arr))
            _finalize_leaf(a, g)

    # head-seeded leaves with no upstream contributions are final now
    for arr, _ in list(leaf_grads.values()):
        _maybe_finalize(arr)

    # Walk reachable nodes newest->oldest; skip nodes with no cotangent.
    for node in sorted(reachable.values(), key=lambda n: n.order,
                       reverse=True):
        if node.vjp_fn is None or all(g is None for g in node.out_grads):
            # visiting still retires this node's pending contributions —
            # a skipped node can never deliver a cotangent later
            for inp in node.inputs:
                if inp._grad_req != "null":
                    pending[id(inp)] -= 1
                    _maybe_finalize(inp)
            continue
        cotangents = tuple(
            jnp.zeros(node.out_protos[k][0], node.out_protos[k][1])
            if g is None else g
            for k, g in enumerate(node.out_grads))
        try:
            in_grads = node.vjp_fn(
                cotangents if node.n_out > 1 else cotangents[0])
        except FloatingPointError as e:
            from . import debug as _debug
            if not _debug.debug_nans_enabled():
                raise
            raise MXNetError(
                f"NaN/Inf produced in backward of op "
                f"'{node.name or node.fn}' (MXTPU_DEBUG_NANS): {e}") from e
        if not isinstance(in_grads, (list, tuple)):
            in_grads = (in_grads,)
        for inp, g in zip(node.inputs, in_grads):
            if g is None:
                continue
            if inp._node is not None and inp._node.vjp_fn is not None:
                # upstream pullbacks are dense jax.vjp closures — a sparse
                # cotangent headed into one must materialize
                if isinstance(g, SparseCotangent):
                    g = g.densify()
                pnode, pidx = inp._node, inp._out_index
                pnode.out_grads[pidx] = _accumulate(pnode.out_grads[pidx], g)
            # an intermediate with attach_grad'd grad_req receives its grad
            # IN ADDITION to propagating upstream (reference autograd.grad
            # supports non-leaf variables)
            if inp._grad_req != "null":
                _leaf_accumulate(inp, g)
        # this node's contributions are delivered: retire them and fire
        # grad-ready hooks for any leaf that just became final — this IS
        # the backward-order firing the overlap scheduler keys off
        for inp in node.inputs:
            if inp._grad_req != "null":
                pending[id(inp)] -= 1
                _maybe_finalize(inp)
        # cotangent slots are consumed by this pass either way; only the
        # pullback/inputs survive under retain_graph
        node.out_grads = [None] * node.n_out
        if not retain_graph:
            node.vjp_fn = None
            node.fn = None      # also blocks replay_function on this graph
            node.inputs = []

    for arr, g in leaf_grads.values():
        _finalize_leaf(arr, g)
    return len(reachable)


def replay_function(heads, variables):
    """Rebuild the pure function variables -> heads from the recorded tape.

    The higher-order-grad path (reference: MXAutogradBackwardEx with
    create_graph, python/mxnet/autograd.py grad()): the imperative tape is
    replayed as a pure jax function so ``jax.vjp`` of it can itself be
    recorded as one tape op — grad-of-grad then falls out of jax's ability
    to differentiate through vjp. Requires nodes that still hold their
    forward ``fn`` (i.e. recorded in this scope, not consumed by a
    non-retaining backward).
    """
    reachable = {}
    stack = [h._node for h in heads if h._node is not None]
    while stack:
        node = stack.pop()
        if node is None or id(node) in reachable:
            continue
        if node.fn is None:
            raise MXNetError(
                "graph was consumed by a previous backward; pass "
                "retain_graph=True / create_graph=True on the earlier call")
        reachable[id(node)] = node
        for inp in node.inputs:
            if inp._node is not None:
                stack.append(inp._node)
    order = sorted(reachable.values(), key=lambda n: n.order)
    var_ids = {id(v): i for i, v in enumerate(variables)}

    def f(*var_datas):
        out_cache = {}

        def val(arr):
            if id(arr) in var_ids:
                return var_datas[var_ids[id(arr)]]
            n = arr._node
            if n is not None and id(n) in out_cache:
                return out_cache[id(n)][arr._out_index]
            return arr._data

        for node in order:
            outs = node.fn(*[val(i) for i in node.inputs])
            if node.n_out == 1:
                outs = (outs,)
            out_cache[id(node)] = outs
        return tuple(val(h) for h in heads)

    return f


def _apply_grad_req(arr, g):
    if g.dtype != arr.dtype:
        g = g.astype(arr.dtype)
    if isinstance(g, SparseCotangent):
        from .ndarray.sparse import RowSparseNDArray
        # a dense variable accumulates densely from its first backward
        # on (zeros made here); a row_sparse one stays O(nnz)
        prev = grad_array(arr) if arr._grad_req == "add" else None
        if isinstance(prev, RowSparseNDArray):
            g = SparseCotangent(prev.indices.data, prev.values.data,
                                g.shape).merge(g)
        elif prev is not None:
            arr._grad = prev.at[g.indices].add(g.values)
            arr._grad_fresh = True
            arr._grad_reduced = False
            return
        g = g.dedup()
        arr._grad = RowSparseNDArray(g.values, g.indices, g.shape, arr._ctx)
    elif arr._grad_req == "add" and arr._grad is not None:
        prev = arr._grad
        from .ndarray.sparse import RowSparseNDArray
        if isinstance(prev, RowSparseNDArray):
            prev = prev.data
        arr._grad = prev + g
    else:
        arr._grad = g
    arr._grad_fresh = True
    arr._grad_reduced = False
