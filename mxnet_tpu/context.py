"""Device contexts: ``mx.cpu()``, ``mx.tpu()`` (and ``mx.gpu()`` alias).

Rebuild of ``python/mxnet/context.py`` (reference): ``Context`` objects with a
``with``-scope "current context" stack. The TPU-native twist: ``device_id``
indexes into ``jax.devices(device_type)``, and placing an NDArray on a context
is a ``jax.device_put``. There are no streams or per-device worker threads to
manage — XLA's async runtime (which replaces ``src/engine/`` wholesale, see
SURVEY.md §1) owns scheduling.
"""
from __future__ import annotations

import os
import threading

import jax

from .base import MXNetError

__all__ = ["Context", "cpu", "gpu", "tpu", "cpu_pinned", "current_context",
           "num_tpus", "num_gpus"]

_DEVTYPE_ALIASES = {
    "cpu": "cpu",
    "cpu_pinned": "cpu",
    # ``gpu`` kept for one-line porting of reference scripts: on this stack
    # the accelerator is the TPU, and nothing else answers for it.
    "gpu": "tpu",
    "tpu": "tpu",
}


class Context:
    """A device context. Reference: python/mxnet/context.py (class Context)."""

    _current = threading.local()

    def __init__(self, device_type, device_id=0):
        if isinstance(device_type, Context):
            device_type, device_id = device_type.device_type, device_type.device_id
        device_type = device_type.lower()
        if device_type not in _DEVTYPE_ALIASES:
            raise MXNetError(f"unknown device type {device_type!r}")
        self.device_type = device_type
        self.device_id = int(device_id)

    # -- jax interop ------------------------------------------------------
    @property
    def jax_device(self):
        """Resolve to a concrete jax.Device.  ``tpu``/``gpu`` mean the TPU
        backend and nothing else: when JAX has none, this raises rather
        than hand back a CPU device under the name ``tpu(0)``."""
        platform = _DEVTYPE_ALIASES[self.device_type]
        try:
            devices = jax.devices(platform)
        except RuntimeError as e:
            found = sorted({d.platform for d in jax.devices()})
            raise MXNetError(
                f"{self}: JAX has no {platform!r} backend in this process "
                f"(found {found}; JAX_PLATFORMS="
                f"{os.environ.get('JAX_PLATFORMS', '')!r}): {e}") from e
        if self.device_id >= len(devices):
            raise MXNetError(
                f"{self} out of range: only {len(devices)} {self.device_type} "
                f"device(s) visible")
        return devices[self.device_id]

    # -- scope handling ---------------------------------------------------
    def __enter__(self):
        if not hasattr(Context._current, "stack"):
            Context._current.stack = []
        Context._current.stack.append(self)
        return self

    def __exit__(self, *exc):
        Context._current.stack.pop()
        return False

    def __eq__(self, other):
        return (isinstance(other, Context)
                and self.device_type == other.device_type
                and self.device_id == other.device_id)

    def __hash__(self):
        return hash((self.device_type, self.device_id))

    def __repr__(self):
        return f"{self.device_type}({self.device_id})"

    def __str__(self):
        return self.__repr__()


def cpu(device_id=0):
    return Context("cpu", device_id)


def cpu_pinned(device_id=0):
    return Context("cpu_pinned", device_id)


def gpu(device_id=0):
    """Accelerator context, kept for script compatibility; same as tpu()."""
    return Context("gpu", device_id)


def tpu(device_id=0):
    """The TPU context — the north-star API (`mx.tpu()`)."""
    return Context("tpu", device_id)


def num_tpus():
    """TPU devices JAX can see in this process; 0 when it has no TPU
    backend (for example under ``JAX_PLATFORMS=cpu``)."""
    try:
        return len(jax.devices("tpu"))
    except RuntimeError:
        return 0


def num_gpus():
    return num_tpus()


def current_context():
    """Reference: python/mxnet/context.py current_context(); defaults to cpu(0)
    upstream — here it defaults to the best available device so that model-zoo
    scripts run on the TPU without a context argument."""
    stack = getattr(Context._current, "stack", None)
    if stack:
        return stack[-1]
    return default_context()


def default_context():
    if num_tpus() > 0:
        return tpu(0)
    return cpu(0)
