"""Device mesh construction and distributed init.

Reference counterpart: the process/topology side of ps-lite + launch.py
(SURVEY.md §2.6): DMLC_ROLE/DMLC_PS_ROOT_URI env rendezvous. TPU-native:
``jax.distributed.initialize`` (honoring both JAX-style and DMLC-style env
vars) and ``jax.sharding.Mesh`` over ICI/DCN.
"""
from __future__ import annotations

import os
import threading

import numpy as _np
import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..base import MXNetError

__all__ = ["make_mesh", "local_mesh", "distributed_init", "mesh_scope",
           "current_mesh", "data_sharding", "replicate_sharding",
           "batch_sharding", "P", "MeshConfig", "mesh_config_from_env",
           "AXIS_DP", "AXIS_TP", "AXIS_PP"]

_STATE = threading.local()

#: Canonical mesh-axis names (ISSUE 11).  Every module that shards or
#: reduces over an axis imports THESE — a hardcoded "dp"/"tp"/"pp"
#: string outside this file is an mxlint HB17 violation: the axis names
#: are MeshConfig's contract, and literal copies rot silently when the
#: mesh layout changes.
AXIS_DP = "dp"      # data parallel: batch split, grad reduce
AXIS_TP = "tp"      # tensor parallel: weight-matrix split (megatron)
AXIS_PP = "pp"      # pipeline parallel: layer stages, microbatch flow


class MeshConfig:
    """One named-axis device-mesh configuration: ``dp x tp x pp``.

    The single source of truth for how the device pool is carved
    (ISSUE 11 tentpole): ``DataParallelTrainer``, ZeRO bucketing, the
    overlap scheduler, checkpoint resharding and elastic rebuild all
    consume a MeshConfig instead of re-deriving axis names/sizes.

    Any axis of size 1 is DISABLED: it does not appear in the built
    ``jax.sharding.Mesh``, so the default ``MeshConfig(dp=N)`` builds
    exactly the ``Mesh(('dp',), N)`` the flat-dp trainer always used —
    ``MXTPU_MESH`` unset is bitwise today's behavior.

    Axis order in the built mesh is ``(pp, dp, tp)`` outermost-first:
    tp is the most-communicating axis and lands on adjacent ICI
    neighbours, pp needs the least bandwidth and spans the outermost
    dimension — the scaling-book layout.  ``stage_mesh(s)`` slices the
    pipeline axis off, returning stage ``s``'s ``dp x tp`` submesh on
    that stage's physical devices (pipeline-STAGED parameters: each
    stage's params exist only on its slice).
    """

    AXES = (AXIS_DP, AXIS_TP, AXIS_PP)

    def __init__(self, dp=1, tp=1, pp=1):
        for name, v in ((AXIS_DP, dp), (AXIS_TP, tp), (AXIS_PP, pp)):
            if not isinstance(v, int) or (v < 1 and v != -1):
                raise MXNetError(
                    f"MeshConfig: axis {name!r} must be a positive int "
                    f"(or -1 to infer dp), got {v!r}")
        if tp == -1 or pp == -1:
            raise MXNetError("MeshConfig: only the dp axis may be -1")
        self.dp, self.tp, self.pp = dp, tp, pp

    # -- construction ----------------------------------------------------
    @classmethod
    def from_spec(cls, spec):
        """Parse a mesh spec string.

        Two grammars (both case-insensitive, whitespace ignored):

        - tagged: ``"dp8"``, ``"dp4tp2"``, ``"dp2tp2pp2"`` — any subset
          of axes, any order, unlisted axes default to 1;
        - positional: ``"2x2x2"`` (``dp x tp x pp``; trailing axes may
          be omitted: ``"4x2"`` = dp4 tp2).
        """
        import re
        s = str(spec).strip().lower().replace(" ", "")
        if not s:
            raise MXNetError("MeshConfig.from_spec: empty spec")
        if re.fullmatch(r"-?\d+(x-?\d+){0,2}", s):
            sizes = [int(t) for t in s.split("x")]
            sizes += [1] * (3 - len(sizes))
            return cls(dp=sizes[0], tp=sizes[1], pp=sizes[2])
        toks = re.findall(r"(dp|tp|pp)(-?\d+)", s)
        if not toks or "".join(t + n for t, n in toks) != s:
            raise MXNetError(
                f"MXTPU_MESH/mesh spec {spec!r} not understood: use "
                f"'dp8', 'dp2tp2pp2' or 'DPxTPxPP' like '2x2x2'")
        axes = {}
        for name, num in toks:
            if name in axes:
                raise MXNetError(f"mesh spec {spec!r}: axis {name!r} "
                                 f"given twice")
            axes[name] = int(num)
        return cls(**axes)

    @classmethod
    def from_env(cls):
        """The active config from ``MXTPU_MESH`` — None when unset (the
        caller falls back to flat dp over all devices, today's
        behavior)."""
        spec = os.environ.get("MXTPU_MESH", "").strip()
        return cls.from_spec(spec) if spec else None

    @classmethod
    def for_mesh(cls, mesh):
        """Derive the config an existing Mesh implies (axes the mesh
        does not name are size 1)."""
        shape = dict(mesh.shape)
        return cls(dp=int(shape.get(AXIS_DP, 1)),
                   tp=int(shape.get(AXIS_TP, 1)),
                   pp=int(shape.get(AXIS_PP, 1)))

    def resolve(self, n_devices):
        """Infer ``dp=-1`` against a device count; returns a concrete
        MeshConfig."""
        if self.dp != -1:
            return self
        denom = self.tp * self.pp
        if n_devices % denom:
            raise MXNetError(
                f"MeshConfig: {n_devices} devices not divisible by "
                f"tp*pp={denom}")
        return MeshConfig(dp=n_devices // denom, tp=self.tp, pp=self.pp)

    # -- introspection ---------------------------------------------------
    @property
    def size(self):
        return self.dp * self.tp * self.pp

    def axis_size(self, axis):
        return {AXIS_DP: self.dp, AXIS_TP: self.tp,
                AXIS_PP: self.pp}[axis]

    def enabled(self, axis):
        return self.axis_size(axis) > 1

    def as_dict(self):
        return {AXIS_DP: self.dp, AXIS_TP: self.tp, AXIS_PP: self.pp}

    def describe(self):
        """Canonical compact spec, e.g. ``"dp8"`` / ``"dp2tp2pp2"`` —
        round-trips through :meth:`from_spec`."""
        out = f"{AXIS_DP}{self.dp}"
        if self.tp > 1:
            out += f"{AXIS_TP}{self.tp}"
        if self.pp > 1:
            out += f"{AXIS_PP}{self.pp}"
        return out

    def __eq__(self, other):
        return isinstance(other, MeshConfig) and \
            self.as_dict() == other.as_dict()

    def __hash__(self):
        return hash((self.dp, self.tp, self.pp))

    def __repr__(self):
        return f"MeshConfig({self.describe()!r})"

    # -- mesh building ---------------------------------------------------
    def _ordered_axes(self):
        """(name, size) outermost-first: pp, dp, tp — disabled axes
        dropped, dp always present (the one axis the flat trainer
        assumes exists)."""
        axes = []
        if self.pp > 1:
            axes.append((AXIS_PP, self.pp))
        axes.append((AXIS_DP, self.dp))
        if self.tp > 1:
            axes.append((AXIS_TP, self.tp))
        return axes

    def _take_devices(self, devices):
        devices = list(devices) if devices is not None else jax.devices()
        cfg = self.resolve(len(devices))
        if cfg.size > len(devices):
            raise MXNetError(
                f"MeshConfig {cfg.describe()} needs {cfg.size} devices, "
                f"only {len(devices)} available")
        return cfg, devices[:cfg.size]

    def build(self, devices=None):
        """The full ``jax.sharding.Mesh`` (first ``size`` devices of the
        pool)."""
        cfg, devs = self._take_devices(devices)
        names = [n for n, _ in cfg._ordered_axes()]
        sizes = [s for _, s in cfg._ordered_axes()]
        arr = _np.asarray(devs).reshape(sizes)
        return Mesh(arr, tuple(names))

    def stage_mesh(self, stage, devices=None):
        """Pipeline stage ``stage``'s ``dp [x tp]`` submesh — the devices
        that stage's parameters, activations and optimizer state live
        on.  With pp disabled there is exactly one stage: the full
        mesh."""
        cfg, devs = self._take_devices(devices)
        if not 0 <= stage < cfg.pp:
            raise MXNetError(f"stage {stage} out of range for "
                             f"pp={cfg.pp}")
        names = [n for n, _ in cfg._ordered_axes()]
        sizes = [s for _, s in cfg._ordered_axes()]
        arr = _np.asarray(devs).reshape(sizes)
        if cfg.pp > 1:
            arr = arr[stage]
            names = names[1:]
        return Mesh(arr, tuple(names))


def mesh_config_from_env(default_devices=None):
    """Resolve the ambient MeshConfig: ``MXTPU_MESH`` when set, else
    flat dp over the whole pool (bitwise today's default)."""
    cfg = MeshConfig.from_env()
    if cfg is None:
        n = len(default_devices if default_devices is not None
                else jax.devices())
        cfg = MeshConfig(dp=n)
    return cfg.resolve(len(default_devices if default_devices is not None
                           else jax.devices()))


def distributed_init(coordinator=None, num_processes=None, process_id=None):
    """Initialize multi-host JAX. Honors DMLC-style env for launcher compat:
    DMLC_PS_ROOT_URI/DMLC_PS_ROOT_PORT -> coordinator, DMLC_NUM_WORKER ->
    num_processes, DMLC_WORKER_ID -> process_id (reference: §2.6 env table).
    """
    if coordinator is None:
        uri = os.environ.get("DMLC_PS_ROOT_URI")
        port = os.environ.get("DMLC_PS_ROOT_PORT", "9000")
        if uri:
            coordinator = f"{uri}:{port}"
    if num_processes is None and "DMLC_NUM_WORKER" in os.environ:
        num_processes = int(os.environ["DMLC_NUM_WORKER"])
    if process_id is None and "DMLC_WORKER_ID" in os.environ:
        process_id = int(os.environ["DMLC_WORKER_ID"])
    if coordinator is None:
        return False  # single process
    jax.distributed.initialize(coordinator_address=coordinator,
                               num_processes=num_processes,
                               process_id=process_id)
    return True


def make_mesh(axes=None, devices=None):
    """Build a Mesh. ``axes`` is a dict name->size (-1 = infer one axis).

    Example: make_mesh({'dp': -1, 'tp': 2}) on 8 devices -> 4x2 mesh.
    Axis order follows insertion order; put the fastest-varying
    (most-communicating, e.g. 'tp') LAST so it lands on adjacent ICI
    neighbours (scaling-book recipe).
    """
    devices = devices if devices is not None else jax.devices()
    n = len(devices)
    axes = dict(axes or {"dp": n})
    sizes = list(axes.values())
    names = list(axes.keys())
    n_infer = sizes.count(-1)
    if n_infer > 1:
        raise MXNetError("at most one mesh axis may be -1")
    known = 1
    for s in sizes:
        if s != -1:
            known *= s
    if n_infer:
        if n % known:
            raise MXNetError(f"{n} devices not divisible by {known}")
        sizes[sizes.index(-1)] = n // known
    total = 1
    for s in sizes:
        total *= s
    if total != n:
        raise MXNetError(f"mesh {dict(zip(names, sizes))} != {n} devices")
    arr = _np.asarray(devices).reshape(sizes)
    return Mesh(arr, tuple(names))


def local_mesh(axes=None):
    return make_mesh(axes, jax.local_devices())


class mesh_scope:
    """with mesh_scope(mesh): ... — sets the ambient mesh used by
    DataParallelTrainer / sharded layers."""

    def __init__(self, mesh):
        self.mesh = mesh

    def __enter__(self):
        stack = getattr(_STATE, "stack", None)
        if stack is None:
            stack = _STATE.stack = []
        stack.append(self.mesh)
        return self.mesh

    def __exit__(self, *exc):
        _STATE.stack.pop()
        return False


def current_mesh():
    stack = getattr(_STATE, "stack", None)
    return stack[-1] if stack else None


def data_sharding(mesh, ndim, axis=0, data_axis="dp"):
    """NamedSharding splitting dim `axis` over the data mesh axis."""
    spec = [None] * ndim
    spec[axis] = data_axis
    return NamedSharding(mesh, P(*spec))


def replicate_sharding(mesh):
    return NamedSharding(mesh, P())


def batch_sharding(mesh, ndim, batch_axis=0, data_axis=None):
    """NamedSharding for an input-batch array of rank ``ndim``.

    Splits the batch axis over the mesh's data axis; rank-1 arrays
    (per-sample label vectors) always split on axis 0 whatever the
    nominal ``batch_axis`` (same convention as
    ``DataParallelTrainer._eff_bax``); scalars replicate.  ``data_axis``
    defaults to ``'dp'`` when the mesh has one, else the first mesh
    axis.  Used by ``io.DevicePrefetcher`` to land prefetched batches
    directly on their step-time sharding — no device-side reshard when
    the step consumes them.
    """
    if data_axis is None:
        data_axis = "dp" if "dp" in mesh.axis_names else mesh.axis_names[0]
    if ndim == 0:
        return NamedSharding(mesh, P())
    ax = batch_axis if ndim > 1 else 0
    if ax >= ndim:
        raise MXNetError(
            f"batch axis {ax} out of range for rank-{ndim} array")
    spec = [None] * ndim
    spec[ax] = data_axis
    return NamedSharding(mesh, P(*spec))
