"""``mxnet_tpu.testing`` — fault-injection + chaos harness.

Production training at pod scale treats failure as the steady state
(ROADMAP north star; arXiv 1909.09756 §5): the only way to trust the
recovery machinery is to provoke failures deterministically.  This
package owns that machinery:

- :mod:`mxnet_tpu.testing.faults` — named fault points instrumented into
  the runtime (checkpoint writer, D2H, PS heartbeats, train step), armed
  via the :func:`~mxnet_tpu.testing.faults.inject` context manager or
  the ``MXTPU_FAULT_INJECT`` env hook.
- :mod:`mxnet_tpu.testing.chaos` — the self-contained kill-and-resume
  smoke scenario ``python -m mxnet_tpu.testing.chaos`` runs.
"""
from . import faults

__all__ = ["faults"]
