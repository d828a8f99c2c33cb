"""FLOP/MFU accounting for ``bench.py`` and ``chip_smoke.py``: the chip
peak table, XLA's cost analysis of a compiled step, the analytic 2*MAC
counts, and :func:`attach_mfu`, which stamps a bench payload with them.

Nothing on the trainer's path reads this module: a running job's
utilization is measured by the benchmark from a device trace
(``device.mfu_pct``, benchmark/readers/device.py), and the trainer
publishes ``train.step_interval_ms`` for the step time.
"""
from __future__ import annotations

import os

__all__ = ["PEAK_BF16", "chip_peak_flops", "compiled_flops",
           "resnet_train_flops_per_img", "bert_train_flops_per_sample",
           "attach_mfu"]

#: Advertised per-chip bf16 peak FLOP/s by device_kind substring (google
#: cloud TPU docs); lowercase match, first hit wins.
PEAK_BF16 = [
    ("v6", 918e12), ("trillium", 918e12),
    ("v5p", 459e12),
    ("v5 lite", 197e12), ("v5e", 197e12), ("v5litepod", 197e12),
    ("v4", 275e12),
    ("v3", 123e12),
    ("v2", 46e12),
]


def _env_peak():
    """``MXTPU_CHIP_PEAK_TFLOPS`` override (TFLOP/s): unknown device
    kinds set the peak explicitly.  None when unset/unparseable."""
    raw = os.environ.get("MXTPU_CHIP_PEAK_TFLOPS", "").strip()
    if not raw:
        return None
    try:
        v = float(raw) * 1e12
    except ValueError:
        return None
    return v if v > 0 else None


def chip_peak_flops(dev=None):
    """Peak bf16 FLOP/s for ``dev`` (default: first jax device); the
    env override wins.  None when unknown — callers must treat that as
    "MFU unmeasurable", never as zero."""
    peak = _env_peak()
    if peak is not None:
        return peak
    if dev is None:
        import jax
        dev = jax.devices()[0]
    kind = getattr(dev, "device_kind", "").lower()
    for sub, peak in PEAK_BF16:
        if sub in kind:
            return peak
    return None


def compiled_flops(jitted, *args):
    """XLA's own FLOP estimate for the compiled step (AOT cost
    analysis).  One lower+compile per call — do it once per compiled
    step, never per step."""
    try:
        cost = jitted.lower(*args).compile().cost_analysis()
        if isinstance(cost, (list, tuple)):
            cost = cost[0] if cost else {}
        f = float(cost.get("flops", -1.0))
        return f if f > 0 else None
    except Exception:  # noqa: BLE001 — cost analysis is best-effort
        return None


def resnet_train_flops_per_img():
    # 4.1 GFLOP fwd at 224^2 (2*MAC convention) * 3 for fwd+bwd
    return 3 * 4.1e9


def bert_train_flops_per_sample(seq, layers=12, d=768, ffn=3072):
    # matmul MACs/token/layer: QKVO 4d^2, FFN 2*d*ffn, attention 2*L*d
    per_tok = layers * (4 * d * d + 2 * d * ffn + 2 * seq * d)
    return 3 * 2 * per_tok * seq  # fwd+bwd ~ 3x fwd; FLOPs = 2*MACs


def attach_mfu(result, flops_per_sample, samples_per_sec, jitted=None,
               jit_args=None):
    """Stamp ``tflops_delivered`` / ``flops_source`` / ``mfu`` /
    ``chip_peak_tflops_bf16`` onto a bench payload — the exact
    bench.py semantics (XLA cost analysis when available and
    ``MXTPU_BENCH_COST_ANALYSIS`` allows it, else the analytic 2*MAC
    count; MFU only when the chip peak is known)."""
    import jax
    analytic = flops_per_sample
    compiled = None
    if jitted is not None and jit_args is not None and \
            os.environ.get("MXTPU_BENCH_COST_ANALYSIS", "1") == "1":
        per_step = compiled_flops(jitted, *jit_args)
        if per_step is not None:
            compiled = per_step
    batch = result.get("batch", 1)
    flops_per_step = compiled if compiled is not None \
        else analytic * batch
    result["tflops_delivered"] = round(
        flops_per_step / batch * samples_per_sec / 1e12, 2)
    result["flops_source"] = "xla_cost_analysis" if compiled is not None \
        else "analytic_2mac"
    peak = chip_peak_flops(jax.devices()[0])
    if peak is not None:
        result["mfu"] = round(
            flops_per_step / batch * samples_per_sec / peak, 4)
        result["chip_peak_tflops_bf16"] = peak / 1e12
    return result
