"""Ring attention: sequence/context parallelism over the mesh 'sp' axis.

NEW capability vs the reference (SURVEY.md §5.7: absent upstream; required
for the long-context/Llama stretch). Design:

  - the sequence axis of Q/K/V is sharded over 'sp'
  - inside shard_map, each device holds its Q block and rotates K/V blocks
    around the ring with lax.ppermute (ICI neighbour exchanges), accumulating
    attention with the numerically-stable running-max/denominator update
    (flash-attention style), so no device ever materializes the full
    (T x T) score matrix
  - causal masking is applied per (q_block, kv_block) pair from ring offsets

This composes with tp ('tp' on heads) and dp in one mesh.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax import shard_map
from jax.sharding import PartitionSpec as P

from ..base import MXNetError

__all__ = ["ring_attention", "ring_attention_local",
           "sequence_parallel_attention"]


def _block_attn(q, k, v, causal, scale):
    """Attention over one (q_block, kv_block) pair via the BLOCKWISE
    streaming kernel (ops.flash_attention._scan_forward): per-device
    memory O(T_local * bk), never the (T_local, T_local) score matrix —
    the flash x ring composition (SURVEY.md §5.7 TPU plan). Returns
    (normalized out, logsumexp) for exact cross-block combination."""
    from ..ops.flash_attention import _pick_block, _scan_forward
    b, h, t, d = q.shape
    lk = k.shape[2]
    bk = _pick_block(lk, 256) or lk
    out, lse = _scan_forward(q.reshape(b * h, t, d),
                             k.reshape(b * h, lk, d),
                             v.reshape(b * h, lk, d), causal, scale, bk)
    return (out.reshape(b, h, t, d),
            lse.reshape(b, h, t))


def _combine(o1, lse1, o2, lse2):
    """Exact merge of two normalized partial attentions via logsumexp;
    a fully-masked block (lse=-inf) contributes exactly zero."""
    lse = jnp.logaddexp(lse1, lse2)
    w1 = jnp.exp(lse1 - lse)[..., None]
    w2 = jnp.exp(lse2 - lse)[..., None]
    return o1 * w1 + o2 * w2, lse


def ring_attention(q, k, v, mesh, axis_name="sp", causal=False, scale=None):
    """q/k/v: (B, H, T, D) jax.Arrays with T sharded over `axis_name`.

    Returns attention output with the same sharding. Collective cost per
    ring step: one neighbour ppermute of the local K/V block — bandwidth
    optimal on an ICI ring (PAPERS.md: 'Exploring the limits of Concurrency
    in ML Training on Google TPUs' motivates overlapping these sends with
    the block compute; XLA pipelines the ppermute against einsum here).
    """
    if scale is None:
        scale = 1.0 / (q.shape[-1] ** 0.5)
    n = mesh.shape[axis_name]

    def local_fn(q_blk, k_blk, v_blk):
        return ring_attention_local(q_blk, k_blk, v_blk, axis_name, n,
                                    causal=causal, scale=scale)

    return _shard_mapped_qkv(local_fn, q, k, v, mesh, axis_name)


def ring_attention_local(q_blk, k_blk, v_blk, axis_name, n_shards,
                         causal=False, scale=None):
    """Ring-attention body for use INSIDE an existing shard_map whose mesh
    binds ``axis_name`` — this is what makes CP composable with dp/tp/pp in
    one SPMD program (e.g. a pipelined stage function that is itself inside
    a dp x tp x sp x pp shard_map). ``ring_attention`` wraps it in its own
    shard_map for standalone use.

    q/k/v blocks: (B, H_local, T_local, D) — this device's sequence shard.
    """
    if scale is None:
        scale = 1.0 / (q_blk.shape[-1] ** 0.5)
    n = n_shards
    idx = lax.axis_index(axis_name)

    # ring step 0 is always the DIAGONAL pair: in-block causal mask
    # handled inside the streaming kernel itself
    o, lse = _block_attn(q_blk, k_blk, v_blk, causal, scale)

    def body(i, carry):
        o, lse, k_cur, v_cur = carry
        perm = [(j, (j + 1) % n) for j in range(n)]
        k_cur = lax.ppermute(k_cur, axis_name, perm)
        v_cur = lax.ppermute(v_cur, axis_name, perm)
        kv_rank = (idx - i - 1) % n
        # off-diagonal pairs are all-or-nothing under causal: past
        # blocks attend fully, future blocks are nulled via lse=-inf
        # (uniform compute keeps the ring SPMD)
        o2, lse2 = _block_attn(q_blk, k_cur, v_cur, False, scale)
        if causal:
            lse2 = jnp.where(kv_rank < idx, lse2,
                             jnp.full_like(lse2, -1e30))
        o, lse = _combine(o, lse, o2, lse2)
        return (o, lse, k_cur, v_cur)

    o, lse, _, _ = lax.fori_loop(0, n - 1, body, (o, lse, k_blk, v_blk))
    # the logsumexp weights are f32; keep the caller's dtype (bf16
    # AMP long-context is exactly this kernel's use case)
    return o.astype(q_blk.dtype)


def _shard_mapped_qkv(local_fn, q, k, v, mesh, axis_name):
    """Shared CP scaffolding (ring + ulysses): sequence-shard q/k/v over
    `axis_name`, run `local_fn` under shard_map, restore the caller's
    layout for eager inputs.

    Eager arrays committed to one device are laid out over the mesh
    first (and the output restored to the caller's layout so eager CP
    composes with unsharded surrounding ops); under jit the constraint
    is compiled in and the output stays sequence-sharded."""
    spec = P(None, None, axis_name, None)
    sharding = jax.sharding.NamedSharding(mesh, spec)
    eager = not isinstance(q, jax.core.Tracer)
    restore = None

    def place(x):
        if isinstance(x, jax.core.Tracer):
            return jax.lax.with_sharding_constraint(x, sharding)
        return jax.device_put(x, sharding)

    if eager and getattr(q, "sharding", None) is not None and \
            not q.sharding.is_equivalent_to(sharding, q.ndim):
        restore = q.sharding
    q, k, v = place(q), place(k), place(v)
    out = shard_map(local_fn, mesh=mesh, in_specs=(spec, spec, spec),
                    out_specs=spec, check_vma=False)(q, k, v)
    if restore is not None:
        out = jax.device_put(out, restore)
    return out


def sequence_parallel_attention(q, k, v, mesh=None, axis_name="sp",
                                causal=True, scale=None):
    """NDArray-level wrapper: gluon attention layers call this when a mesh
    with an 'sp' axis is ambient (exposed as
    gluon.contrib.nn.SelfAttention(context_parallel=True))."""
    from ..ndarray.ndarray import NDArray, apply_nary
    from .mesh import current_mesh
    mesh = mesh or current_mesh()
    if mesh is None or axis_name not in mesh.shape:
        raise MXNetError("sequence_parallel_attention needs an ambient mesh "
                         f"with a '{axis_name}' axis")

    def fn(qa, ka, va):
        return ring_attention(qa, ka, va, mesh, axis_name, causal, scale)
    return apply_nary(fn, [q, k, v], name="ring_attention")
