"""``mx.runtime`` — feature detection + XLA scheduler flag plumbing.

Reference: python/mxnet/runtime.py over src/libinfo.cc feature flags
("CUDA", "CUDNN", "MKLDNN", ...). The TPU rebuild reports its own substrate,
and additionally owns the XLA *latency-hiding scheduler* flags
(:func:`lhs_flags` / ``MXTPU_LHS=1``) that let the compiler sink the
backward-overlapped gradient collectives (parallel/overlap.py, ISSUE 5)
under remaining backprop compute.
"""
from __future__ import annotations

import os

import jax

__all__ = ["Feature", "Features", "feature_list", "lhs_flags",
           "apply_lhs_flags", "steps_per_call", "enable_compile_cache"]


def enable_compile_cache():
    """Point JAX's persistent compilation cache at one fixed place and
    return that directory.  ``JAX_COMPILATION_CACHE_DIR``, when the
    caller set it, already is that place — JAX reads it itself and this
    touches no setting; otherwise the cache goes to ``.jax_cache`` beside
    the package (the checkout root).  The path never carries a temporary
    name, a pid or a time: a directory that moves is a cache that never
    hits.  Call before the first compile."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if path:
        return path
    path = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def steps_per_call():
    """Training steps lowered into ONE compiled dispatch
    (``MXTPU_STEPS_PER_CALL``, default 1 = today's one-dispatch-per-step
    behavior — the kill switch, same semantics as ``MXTPU_FUSED_STEP``).
    K > 1 makes K-step-capable loops (``estimator.fit`` over a
    ``DataParallelTrainer``) drive
    ``DataParallelTrainer.step_multi`` — K steps scanned device-resident
    per host dispatch, so the per-step eager dispatch + program
    re-entry tax is paid once per K steps (arXiv:2011.03641 host-bound
    concurrency ceiling; arXiv:1909.09756 keeps many steps device-
    resident per launch)."""
    from .base import MXNetError
    raw = os.environ.get("MXTPU_STEPS_PER_CALL", "1")
    try:
        k = int(raw)
    except ValueError:
        raise MXNetError(
            f"MXTPU_STEPS_PER_CALL={raw!r}: expected an integer >= 1")
    if k < 1:
        raise MXNetError(
            f"MXTPU_STEPS_PER_CALL must be >= 1, got {k}")
    return k


# The flag set the TPU scaling playbook enables for comm/compute overlap
# (arXiv:2011.03641's "overlap gradient summation with backprop", done by
# the compiler): the latency-hiding scheduler itself plus async lowering
# of the collectives it reorders.  Harmless elsewhere: XLA ignores
# backend-inapplicable flags on CPU/GPU backends.
_LHS_FLAGS = (
    "--xla_tpu_enable_latency_hiding_scheduler=true",
    "--xla_enable_async_all_gather=true",
    "--xla_enable_async_collective_permute=true",
    "--xla_tpu_enable_async_collective_fusion=true",
    "--xla_tpu_enable_async_collective_fusion_fuse_all_gather=true",
)


def lhs_flags():
    """The XLA latency-hiding-scheduler flag strings (tuple).  These let
    XLA launch a bucket's reduce-scatter as soon as its gradients exist
    and hide the wire time under remaining backward compute — the
    compiler half of the backward-overlapped comm pipeline (the graph
    half is the backward-ordered ``zero.BucketPlan``)."""
    return _LHS_FLAGS


def _tpu_backend_plausible(env):
    """True when the process can plausibly initialize a TPU backend.
    The gate matters: CPU/GPU builds of XLA *fatally abort* on unknown
    ``--xla_tpu_*`` flags, so the LHS flags may only go into XLA_FLAGS
    where libtpu will consume them."""
    platforms = env.get("JAX_PLATFORMS", "")
    if "tpu" in platforms:
        return True
    if platforms:            # explicitly pinned elsewhere (cpu, cuda)
        return False
    import importlib.util
    return importlib.util.find_spec("libtpu") is not None


def apply_lhs_flags(env=None, force=False):
    """Append :func:`lhs_flags` to ``XLA_FLAGS`` in ``env`` (default
    ``os.environ``), skipping flags already present.  Must run BEFORE
    the XLA backend initializes (first jax computation) to take effect;
    ``MXTPU_LHS=1`` triggers this automatically at ``import mxnet_tpu``.
    No-op on non-TPU hosts unless ``force=True`` — the flags are
    TPU-backend-specific and a CPU/GPU XLA build aborts on them.
    Returns the resulting ``XLA_FLAGS`` value."""
    env = os.environ if env is None else env
    current = env.get("XLA_FLAGS", "")
    if not force and not _tpu_backend_plausible(env):
        return current
    missing = [f for f in _LHS_FLAGS
               if f.split("=")[0] not in current]
    if missing:
        current = (current + " " + " ".join(missing)).strip()
        env["XLA_FLAGS"] = current
    return current


class Feature:
    def __init__(self, name, enabled):
        self.name = name
        self._enabled = enabled

    @property
    def enabled(self):
        return self._enabled

    def __repr__(self):
        return f"[{'✔' if self._enabled else '✖'} {self.name}]"


def _detect():
    try:
        backend = jax.default_backend()
    except Exception:
        backend = "cpu"
    devs = 0
    try:
        devs = len(jax.devices())
    except Exception:
        pass
    feats = {
        "TPU": backend not in ("cpu",),
        "XLA": True,
        "JAX": True,
        "PALLAS": True,
        "BF16": True,
        "INT64_TENSOR_SIZE": True,
        "DIST_KVSTORE": True,
        "CUDA": False,
        "CUDNN": False,
        "NCCL": False,
        "MKLDNN": False,
        "OPENCV": _has_cv(),
        "SIGNAL_HANDLER": True,
        "NATIVE_IO": _has_native_io(),
    }
    return {k: Feature(k, v) for k, v in feats.items()}


def _has_cv():
    try:
        import cv2  # noqa: F401
        return True
    except ImportError:
        return False


def _has_native_io():
    try:
        from .utils import native
        return native.available()
    except Exception:
        return False


class Features(dict):
    def __init__(self):
        super().__init__(_detect())

    def is_enabled(self, name):
        feat = self.get(name.upper())
        return bool(feat and feat.enabled)

    def __repr__(self):
        return "[" + ", ".join(repr(v) for v in self.values()) + "]"


def feature_list():
    return list(Features().values())
