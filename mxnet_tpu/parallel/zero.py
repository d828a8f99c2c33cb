"""Bucketed sharded gradient sync + ZeRO-1 optimizer-state sharding.

The data-parallel hot path used to sync gradients with a full-replica
``lax.psum`` and keep a full optimizer-state copy on every chip.  This
module provides the pieces that replace it (ISSUE 3 tentpole):

- :class:`BucketPlan` — host-side planning that flattens all eligible
  parameters into a few size-bounded flat f32 buckets
  (``MXTPU_COMM_BUCKET_MB``, default 32), so the per-step collectives
  are few and large instead of one small ring per tensor (the
  BIGARRAY_BOUND coalescing idea, applied in-graph).
- :func:`reduce_scatter_bucket` — the per-bucket gradient collective,
  run inside ``shard_map`` over the ``dp`` axis: each chip contributes
  its *local* gradient and receives only its 1/N shard of the mean —
  a true reduce-scatter, optionally with the payload quantized on the
  wire (``MXTPU_COMM_DTYPE=bf16|int8``; int8 is stochastic-rounding
  with one scale per (chip, bucket), EQuARX-style — arXiv:2506.17615,
  PAPERS.md row 9).  The updated-parameter all-gather that completes
  the ZeRO-1 pipeline is a plain ``lax.all_gather`` (params must come
  back exact; only the gradient payload is quantizable).
- :func:`comm_block` — the schema of ``DataParallelTrainer.comm_stats``,
  regression-tested in tier-1 even on CPU (zeros are fine).

ZeRO-1 memory math (fp32, N = dp size): momentum-SGD keeps 4 B/param of
optimizer state, Adam 8 B/param — replicated on every chip before; with
the bucket shards each chip holds 1/N of it (plus its 1/N update
compute).  Parameters stay replicated (ZeRO *stage 1*).
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
from jax import lax

from ..base import MXNetError
from .mesh import AXIS_DP

__all__ = ["BucketPlan", "bucket_bound_bytes", "comm_dtype",
           "sharded_sync_enabled", "overlap_comm_enabled",
           "reduce_scatter_bucket", "quantize_int8", "dequantize_int8",
           "int8_roundtrip_error", "comm_block", "ZERO1_RULES"]

#: fused-rule kernels that are elementwise in the parameter, so the
#: update can run on an arbitrary flat shard of the bucket.  lamb/lars
#: need per-parameter norms and keep the replicated psum path.
ZERO1_RULES = frozenset({"sgd", "nag", "adam", "adamw", "rmsprop"})


def bucket_bound_bytes():
    """Bucket size bound in bytes (``MXTPU_COMM_BUCKET_MB``, default 32)."""
    return int(float(os.environ.get("MXTPU_COMM_BUCKET_MB", "32"))
               * 1024 * 1024)


def comm_dtype():
    """Wire dtype for the gradient reduce-scatter: ``"fp32"`` (default),
    ``"bf16"`` or ``"int8"`` via ``MXTPU_COMM_DTYPE``."""
    mode = os.environ.get("MXTPU_COMM_DTYPE", "fp32").lower() or "fp32"
    if mode not in ("fp32", "float32", "bf16", "bfloat16", "int8"):
        raise MXNetError(
            f"MXTPU_COMM_DTYPE={mode!r}: expected fp32|bf16|int8")
    return {"float32": "fp32", "bfloat16": "bf16"}.get(mode, mode)


def sharded_sync_enabled():
    """Kill switch: ``MXTPU_SHARDED_SYNC=0`` forces the legacy full
    psum + replicated-update path even when ``shard_updates=True``."""
    return os.environ.get("MXTPU_SHARDED_SYNC", "1") != "0"


def overlap_comm_enabled():
    """Backward-overlapped gradient communication (ISSUE 5 tentpole):
    ``MXTPU_OVERLAP_COMM=0`` kills the overlap — bucket plans fall back
    to declaration-order fill and the eager OverlapScheduler stands
    down, reproducing the PR 3 monolithic-sync behavior bitwise."""
    return os.environ.get("MXTPU_OVERLAP_COMM", "1") != "0"


class BucketPlan:
    """Greedy coalescing of parameter tensors into flat f32 buckets.

    Parameters are filled in order into buckets of at most
    ``bound_bytes`` of f32 payload (a single tensor larger than the
    bound gets its own bucket), and every bucket is zero-padded so its
    flat length divides ``dp`` — each chip's shard is exactly
    ``length // dp`` elements, no edge-chip special case.

    ``fill_order`` (ISSUE 5 tentpole) is a permutation of parameter
    indices in expected *backward gradient-ready* order
    (reverse-topological: parameters used last in the forward first).
    Buckets are filled in that order, so during backprop bucket 0's
    gradients finish first, bucket 1's next, ... — each bucket's
    reduce-scatter can launch while the rest of the backward is still
    computing (:attr:`ready_order`).  ``None`` keeps declaration-order
    fill (the PR 3 monolithic layout; ``MXTPU_OVERLAP_COMM=0``).
    """

    def __init__(self, shapes, dp, bound_bytes=None, fill_order=None):
        if dp < 1:
            raise MXNetError(f"BucketPlan: dp must be >= 1, got {dp}")
        bound = bound_bytes if bound_bytes is not None \
            else bucket_bound_bytes()
        bound_elems = max(1, bound // 4)          # f32 on-wire elements
        self.dp = int(dp)
        self.shapes = [tuple(s) for s in shapes]
        sizes = []
        for s in self.shapes:
            n = 1
            for d in s:
                n *= int(d)
            sizes.append(n)
        self.sizes = sizes
        if fill_order is None:
            order = list(range(len(sizes)))
            self.fill_order = None
        else:
            order = [int(i) for i in fill_order]
            if sorted(order) != list(range(len(sizes))):
                raise MXNetError(
                    f"BucketPlan: fill_order must be a permutation of "
                    f"0..{len(sizes) - 1}, got {fill_order!r}")
            self.fill_order = tuple(order)
        self.buckets = []          # list of lists of param indices
        cur, cur_n = [], 0
        for i in order:
            n = sizes[i]
            if cur and cur_n + n > bound_elems:
                self.buckets.append(cur)
                cur, cur_n = [], 0
            cur.append(i)
            cur_n += n
        if cur:
            self.buckets.append(cur)
        self.lengths = []          # padded flat length per bucket
        self.offsets = [None] * len(sizes)   # (bucket_id, offset)
        for b, idxs in enumerate(self.buckets):
            off = 0
            for i in idxs:
                self.offsets[i] = (b, off)
                off += sizes[i]
            pad = (-off) % self.dp
            self.lengths.append(off + pad)

    @property
    def n_buckets(self):
        return len(self.buckets)

    @property
    def ready_order(self):
        """Bucket ids in backward gradient-completion order.  Buckets are
        created in fill order, so when the plan was built with a
        backward ``fill_order`` this is simply ``(0, 1, ...)`` — bucket 0
        completes (and can launch its reduce-scatter) first.  Without a
        ``fill_order`` completion order is unknown; the same tuple is
        returned as the monolithic-dispatch order."""
        return tuple(range(self.n_buckets))

    def shard_length(self, b):
        return self.lengths[b] // self.dp

    def param_span(self, i):
        """``(bucket_id, offset, size)`` of parameter ``i``'s span in
        bucket space — the state-resharding primitive
        (docs/FAULT_TOLERANCE.md): checkpoint save slices bucket-space
        optimizer-state vectors back to per-parameter arrays with this,
        and restore re-flattens them into whatever dp size's plan the
        resumed run built (padding never hits disk)."""
        b, off = self.offsets[i]
        return b, off, self.sizes[i]

    def flatten(self, arrays):
        """Per-bucket flat f32 arrays (concat in plan order + zero pad)."""
        out = []
        for b, idxs in enumerate(self.buckets):
            parts = [jnp.ravel(arrays[i]).astype(jnp.float32)
                     for i in idxs]
            flat = parts[0] if len(parts) == 1 else jnp.concatenate(parts)
            pad = self.lengths[b] - flat.shape[0]
            if pad:
                flat = jnp.concatenate([flat, jnp.zeros(pad, jnp.float32)])
            out.append(flat)
        return out

    def unflatten(self, flats, like):
        """Inverse of :meth:`flatten`: per-parameter arrays with the
        shapes of the plan and the dtypes of ``like``."""
        out = [None] * len(self.shapes)
        for i, (b, off) in enumerate(self.offsets):
            n = self.sizes[i]
            out[i] = flats[b][off:off + n].reshape(self.shapes[i]) \
                .astype(like[i].dtype)
        return out

    # -- wire accounting (static, per step) -----------------------------
    def grad_bytes_fp32(self):
        return 4 * sum(self.lengths)

    def wire_bytes(self, mode):
        """Per-chip gradient payload put on the wire by one reduce-
        scatter round, after quantization."""
        per_elem = {"fp32": 4, "bf16": 2, "int8": 1}[mode]
        scales = 4 * self.n_buckets if mode == "int8" else 0
        return per_elem * sum(self.lengths) + scales


# ---------------------------------------------------------------------------
# quantization (int8, stochastic rounding, one scale per chip x bucket)
# ---------------------------------------------------------------------------

# the SR core moved to ops/quant_matmul (ISSUE 20): the wire (this
# module) and the training-compute path share ONE unbiased rounding
# implementation; these names stay importable here for PR 3 callers.
from ..ops.quant_matmul import (quantize_sr_int8 as quantize_int8,  # noqa: E402,F401
                                dequantize_int8)


def int8_roundtrip_error(flat, key):
    """Measured (not assumed) per-bucket max relative quantization error
    ``max|deq - x| / max|x|`` — the number the parity test reports."""
    q, scale = quantize_int8(flat, key)
    err = jnp.max(jnp.abs(dequantize_int8(q, scale) - flat))
    return err / jnp.maximum(jnp.max(jnp.abs(flat)), 1e-30)


def reduce_scatter_bucket(flat, key, dp, mode="fp32",
                          axis=AXIS_DP):
    """Mean-reduce one bucket across ``dp`` chips, returning this chip's
    1/dp shard.  Must run inside ``shard_map`` with ``axis`` bound;
    ``flat`` is the chip's LOCAL gradient bucket (f32, length % dp == 0).

    - ``fp32``: ``lax.psum_scatter`` (the plain reduce-scatter).
    - ``bf16``: payload cast to bf16 before the collective (half the
      wire bytes; accumulation happens in bf16 — measured error, not
      assumed: see tests/test_sharded_sync.py).
    - ``int8``: stochastic-rounding int8 codes with a per-(chip,bucket)
      f32 scale, exchanged shard-to-shard via ``all_to_all`` (1/4 the
      f32 wire bytes), then dequantized and accumulated in f32 — the
      wire carries int8 but no int8 arithmetic ever overflows.
    """
    if mode == "fp32":
        return lax.psum_scatter(flat, axis, tiled=True) / dp
    if mode == "bf16":
        # bf16 keeps f32's exponent range, so the wire cast needs no
        # amax scale — exempt from the HB21 scaled-cast discipline
        shard = lax.psum_scatter(
            flat.astype(jnp.bfloat16),  # mxlint: disable=HB21
            axis, tiled=True)
        return shard.astype(jnp.float32) / dp
    if mode == "int8":
        q, scale = quantize_int8(flat, key)
        # (dp, L/dp) int8: row j goes to chip j; after all_to_all each
        # chip holds every peer's codes for its own shard
        q = lax.all_to_all(q.reshape(dp, -1), axis, split_axis=0,
                           concat_axis=0, tiled=False)
        scales = lax.all_gather(scale, axis, tiled=False)   # (dp,)
        deq = jnp.sum(q.astype(jnp.float32) * scales.reshape(dp, 1),
                      axis=0)
        return deq / dp
    raise MXNetError(f"unknown comm dtype {mode!r}")


# ---------------------------------------------------------------------------
# the `comm` observability block (DataParallelTrainer.comm_stats)
# ---------------------------------------------------------------------------

def comm_block(dp=1, wire_dtype="fp32", buckets=0, bucket_mb=None,
               bytes_reduced_per_step=0, bytes_gathered_per_step=0,
               grad_bytes_fp32=0, collective_ms=None, est_ici_gb_s=None,
               overlap_efficiency=None, zero1=False,
               state_bytes_per_chip=0, state_bytes_replicated=0,
               overlap_comm=False, exposed_comm_ms=None,
               overlap_frac=None):
    """The per-step ``comm`` block schema.  Every field is always
    present so tier-1 regression-tests the shape
    (tests/test_sharded_sync.py) without needing a multichip host — but
    MEASURED fields (``collective_ms``, ``est_ici_gb_s``,
    ``overlap_efficiency``, ``exposed_comm_ms``, ``overlap_frac``) are
    ``null`` when nothing was measured (CPU / dp=1 / probe skipped)
    instead of 0: the rounds-4/5 silent CPU fallback taught us that a
    zero in a measured field reads as "measured: no comm cost", which
    is a lie (ISSUE 6 honesty fix).  Static wire accounting stays
    integer-zeros — those are genuinely computed, not measured.

    ``exposed_comm_ms`` / ``overlap_frac`` (ISSUE 5) come from the
    with-vs-without-overlap probe
    (``DataParallelTrainer.overlap_probe``): exposed = time the
    overlapped step still spends on communication beyond its pure
    compute, overlap_frac = 1 - exposed / total serialized comm."""
    def _r(x, n):
        return None if x is None else round(float(x), n)

    return {
        "zero1": bool(zero1),
        "dp": int(dp),
        "wire_dtype": str(wire_dtype),
        "buckets": int(buckets),
        "bucket_mb": float(bucket_mb if bucket_mb is not None
                           else bucket_bound_bytes() / (1024 * 1024)),
        "bytes_reduced_per_step": int(bytes_reduced_per_step),
        "bytes_gathered_per_step": int(bytes_gathered_per_step),
        "grad_bytes_fp32": int(grad_bytes_fp32),
        "collective_ms": _r(collective_ms, 3),
        "est_ici_gb_s": _r(est_ici_gb_s, 2),
        "overlap_efficiency": _r(overlap_efficiency, 4),
        "overlap_comm": bool(overlap_comm),
        "exposed_comm_ms": _r(exposed_comm_ms, 3),
        "overlap_frac": _r(overlap_frac, 4),
        "state_bytes_per_chip": int(state_bytes_per_chip),
        "state_bytes_replicated": int(state_bytes_replicated),
    }
