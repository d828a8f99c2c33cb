"""Mixture-of-experts layers: two of them, for two regimes.

1. ``moe_apply`` / ``MoEDense`` — GShard top-1 routing with a static
   capacity (SURVEY.md §2.5 "EP/MoE" row; absent upstream).  Dispatch and
   combine are einsums over a dense (tokens, experts, capacity) one-hot, so
   every shape is static, tokens over an expert's capacity are dropped, and
   with the expert weights sharded ``P('ep', ...)`` XLA derives the
   all-to-all from the sharding algebra.  Functional parameters, not a
   ``HybridBlock``; used by the multi-chip dry run.

2. ``route_sigmoid_top_k`` / ``route_softmax_top_k`` /
   ``dropless_moe_apply`` — dropless top-k routing for a layer that holds a
   share of the experts (DeepSeek-V3-style: sigmoid scores, a selection
   bias, normalised and scaled weights; or a plain softmax over all
   experts, its top-k renormalised).  The layer is
   told which ``held`` of the ``n_routed_experts`` live here
   (``expert_offset``), routes over all of them, and computes its own
   experts' part of the result; assignments to absent experts are left out.
   No capacity, no drop: assignments are sorted by expert, the rows of held
   experts gathered into a buffer that takes the worst case
   (``tokens * min(top_k, held)`` rows), and the expert products run over
   ``group_sizes`` (``ops.grouped_matmul``), doing work for the rows that
   are routed, not for the buffer.  On one chip it runs without its
   exchange; nothing here stands in for absent chips.  The Gluon block
   around it is ``gluon.model_zoo.nlp.deepseek_v3.MoEBlock``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from .. import telemetry as _telem
from ..base import MXNetError

__all__ = ["moe_apply", "MoEDense", "load_balance_loss",
           "route_sigmoid_top_k", "route_softmax_top_k",
           "dropless_moe_apply", "buffer_rows"]


def _top1_dispatch(logits, capacity):
    """Top-1 routing with static capacity (GShard §3.2).

    logits: (T, E). Returns dispatch (T, E, C) float 0/1, combine
    (T, E, C) float (gate-weighted dispatch), plus aux tensors for the
    load-balancing loss.
    """
    T, E = logits.shape
    gates = jax.nn.softmax(logits, axis=-1)          # (T, E)
    expert = jnp.argmax(gates, axis=-1)              # (T,)
    gate = jnp.take_along_axis(gates, expert[:, None], axis=-1)[:, 0]
    mask = jax.nn.one_hot(expert, E)                 # (T, E)
    # position of each token within its expert's queue
    pos = jnp.cumsum(mask, axis=0) * mask            # 1-based where routed
    keep = (pos <= capacity) & (mask > 0)            # drop overflow tokens
    pos0 = jnp.clip(pos - 1, 0, capacity - 1).astype(jnp.int32)
    dispatch = (keep[..., None] *
                jax.nn.one_hot(pos0, capacity)).astype(logits.dtype)
    combine = dispatch * gate[:, None, None]
    return dispatch, combine, gates, mask


def load_balance_loss(gates, mask):
    """GShard aux loss: E * sum_e (mean gate_e * mean routed_e)."""
    E = gates.shape[-1]
    density = jnp.mean(mask, axis=0)                 # fraction routed
    density_proxy = jnp.mean(gates, axis=0)          # mean gate prob
    return E * jnp.sum(density * density_proxy)


def moe_apply(x, router_w, w_up, w_down, *, capacity_factor=1.25,
              activation=jax.nn.gelu):
    """Top-1 MoE FFN over tokens.

    x: (T, d); router_w: (d, E); w_up: (E, d, h); w_down: (E, h, d).
    Returns (y (T, d), aux_loss scalar). Under jit with w_up/w_down sharded
    P('ep', ...) the per-expert einsums shard over 'ep' and XLA inserts the
    dispatch all-to-all.
    """
    T, d = x.shape
    E = router_w.shape[-1]
    capacity = max(1, int(capacity_factor * T / E))
    logits = x @ router_w                            # (T, E)
    dispatch, combine, gates, mask = _top1_dispatch(logits, capacity)
    expert_in = jnp.einsum("tec,td->ecd", dispatch, x)     # (E, C, d)
    h = activation(jnp.einsum("ecd,edh->ech", expert_in, w_up))
    expert_out = jnp.einsum("ech,ehd->ecd", h, w_down)     # (E, C, d)
    y = jnp.einsum("tec,ecd->td", combine, expert_out)
    return y, load_balance_loss(gates, mask)


class MoEDense:
    """Gluon-flavoured MoE FFN block (functional params, shard-spec'd).

    Deliberately NOT a HybridBlock: MoE lives inside fused jitted steps
    (DataParallelTrainer / llama), where parameters flow functionally. Use
    ``init_params(key)`` then ``apply(params, x)``; ``shard_specs()`` gives
    the 'ep' PartitionSpecs for each weight.
    """

    def __init__(self, hidden_size, ffn_size, num_experts,
                 capacity_factor=1.25):
        if num_experts < 1:
            raise MXNetError("num_experts must be >= 1")
        self.hidden_size = hidden_size
        self.ffn_size = ffn_size
        self.num_experts = num_experts
        self.capacity_factor = capacity_factor

    def init_params(self, key):
        kr, ku, kd = jax.random.split(key, 3)
        d, h, E = self.hidden_size, self.ffn_size, self.num_experts
        scale = d ** -0.5
        return {
            "router": jax.random.normal(kr, (d, E)) * scale,
            "w_up": jax.random.normal(ku, (E, d, h)) * scale,
            "w_down": jax.random.normal(kd, (E, h, d)) * (h ** -0.5),
        }

    def shard_specs(self, axis="ep"):
        return {
            "router": P(),
            "w_up": P(axis, None, None),
            "w_down": P(axis, None, None),
        }

    def apply(self, params, x):
        """x: (..., d) — flattened to tokens internally."""
        lead = x.shape[:-1]
        tokens = x.reshape(-1, x.shape[-1])
        y, aux = moe_apply(tokens, params["router"], params["w_up"],
                           params["w_down"],
                           capacity_factor=self.capacity_factor)
        return y.reshape(lead + (x.shape[-1],)), aux


# ---------------------------------------------------------------------------
# Dropless top-k over a held share of the experts
# ---------------------------------------------------------------------------

def route_sigmoid_top_k(x, router_w, bias, top_k, scale=1.0,
                        norm_topk_prob=True):
    """DeepSeek-V3's ``noaux_tc`` router with trivial groups.

    x: (T, d); router_w: (E, d), one row an expert (the ``nn.Dense``
    layout); bias: (E,), the ``e_score_correction_bias``, which takes part
    in the selection only.  Scores are ``sigmoid(x @ router_w.T)`` in
    float32 at full matmul precision; the ``top_k`` of ``scores + bias`` are
    chosen; the weights are the *unbiased* scores of the chosen, divided by
    their sum (``norm_topk_prob``) and multiplied by ``scale``.  Returns
    ``(experts (T, top_k) int32, weights (T, top_k) float32)``."""
    with jax.named_scope("moe.route"):
        scores = jax.nn.sigmoid(jnp.matmul(
            x.astype(jnp.float32), router_w.astype(jnp.float32).T,
            precision=lax.Precision.HIGHEST))
        _, experts = lax.top_k(
            lax.stop_gradient(scores) + bias.astype(jnp.float32), top_k)
        weights = jnp.take_along_axis(scores, experts, axis=-1)
        if norm_topk_prob:
            weights = weights / (jnp.sum(weights, -1, keepdims=True) + 1e-20)
        return experts.astype(jnp.int32), weights * scale


def route_softmax_top_k(x, router_w, top_k, norm_topk_prob=True):
    """A softmax router (``model_type: KeyeVL2``, the Qwen-MoE lineage): the
    gates are ``softmax(x @ router_w.T)`` over all experts, float32 at full
    matmul precision; the ``top_k`` largest are chosen and, with
    ``norm_topk_prob``, divided by their sum.  No bias, no scale.  x: (T,
    d); router_w: (E, d).  Returns ``(experts (T, top_k) int32, weights (T,
    top_k) float32)`` as :func:`route_sigmoid_top_k` does."""
    with jax.named_scope("moe.route"):
        gates = jax.nn.softmax(jnp.matmul(
            x.astype(jnp.float32), router_w.astype(jnp.float32).T,
            precision=lax.Precision.HIGHEST), axis=-1)
        _, experts = lax.top_k(lax.stop_gradient(gates), top_k)
        weights = jnp.take_along_axis(gates, experts, axis=-1)
        if norm_topk_prob:
            weights = weights / (jnp.sum(weights, -1, keepdims=True) + 1e-20)
        return experts.astype(jnp.int32), weights


def buffer_rows(tokens, top_k, held):
    """Rows of the dispatch buffer: the worst case, every token sending all
    it can (it chooses ``top_k`` distinct experts) to experts held here."""
    return tokens * min(top_k, held)


# The plan of one layer's dispatch, a tuple of index arrays (no gradient):
#   choice_of_row (R,)   the flat choice t * k + j that sits in each row
#   row_is_routed (R,)   False for the rows past the last routed one
#   row_of_choice (T, k) where each choice landed (0 where not held)
#   choice_is_held (T, k)

@jax.custom_vjp
def _dispatch(x, plan):
    """``buffer[r] = x[token of the choice in row r]`` for the routed rows,
    zeros past them.  Its transpose is written as a gather too (a row of
    the buffer belongs to exactly one choice), which the scatter-add that
    autodiff would derive is not."""
    choice_of_row, row_is_routed, row_of_choice, _ = plan
    k = row_of_choice.shape[1]
    return jnp.where(row_is_routed[:, None], x[choice_of_row // k], 0)


def _dispatch_fwd(x, plan):
    return _dispatch(x, plan), plan


def _dispatch_bwd(plan, g):
    _, _, row_of_choice, choice_is_held = plan
    dx = jnp.sum(jnp.where(choice_is_held[..., None],
                           g[row_of_choice].astype(jnp.float32), 0), axis=1)
    return dx.astype(g.dtype), None


_dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@jax.custom_vjp
def _combine(buffer, weights, plan):
    """``out[t] = sum_j weights[t, j] * buffer[row_of_choice[t, j]]`` over
    the choices held here, summed in float32."""
    _, _, row_of_choice, choice_is_held = plan
    picked = buffer[row_of_choice].astype(jnp.float32)     # (T, k, d)
    w = jnp.where(choice_is_held, weights.astype(jnp.float32), 0)
    return jnp.sum(picked * w[..., None], axis=1).astype(buffer.dtype)


def _combine_fwd(buffer, weights, plan):
    return _combine(buffer, weights, plan), (buffer, weights, plan)


def _combine_bwd(res, g):
    buffer, weights, plan = res
    choice_of_row, row_is_routed, row_of_choice, choice_is_held = plan
    k = weights.shape[1]
    w_of_row = jnp.where(row_is_routed,
                         weights.reshape(-1)[choice_of_row], 0)
    dbuffer = (g[choice_of_row // k].astype(jnp.float32)
               * w_of_row.astype(jnp.float32)[:, None]).astype(buffer.dtype)
    dweights = jnp.where(
        choice_is_held,
        jnp.sum(buffer[row_of_choice].astype(jnp.float32)
                * g.astype(jnp.float32)[:, None, :], axis=-1), 0)
    return dbuffer, dweights.astype(weights.dtype), None


_combine.defvjp(_combine_fwd, _combine_bwd)


def dropless_moe_apply(x, experts, weights, w_gate, w_up, w_down, *,
                       expert_offset=0):
    """The routed experts' part of a SwiGLU expert layer, for the experts
    held here.

    x: (T, d); experts: (T, k) int32 ids over all routed experts; weights:
    (T, k); w_gate, w_up: (held, d, h); w_down: (held, h, d), the weights of
    experts ``expert_offset .. expert_offset + held``.  Returns (T, d):
    ``sum_{j: experts[t, j] held} weights[t, j] * E_j(x[t])``.  A choice of
    an absent expert adds nothing; no choice of a held expert is dropped.
    """
    from ..ops.grouped_matmul import grouped_matmul
    tokens, k = experts.shape
    held = w_gate.shape[0]
    rows = buffer_rows(tokens, k, held)
    _telem.inc("moe.layers")
    _telem.set_gauge("moe.experts_held", held)
    _telem.set_gauge("moe.rows_buffer", rows)
    _telem.set_gauge("moe.top_k", k)

    with jax.named_scope("moe.dispatch"):
        local = experts - expert_offset
        choice_is_held = (local >= 0) & (local < held)          # (T, k)
        # absent experts sort behind every held one; the sort is stable, so
        # inside an expert's group the rows stay in token order
        key = jnp.where(choice_is_held, local, held).reshape(-1)
        order = jnp.argsort(key, stable=True).astype(jnp.int32)
        group_sizes = jnp.sum(
            key[:, None] == jnp.arange(held, dtype=key.dtype)[None],
            axis=0, dtype=jnp.int32)
        # where each choice landed: the inverse of the sort
        row_of_choice = jnp.zeros(tokens * k, jnp.int32).at[order].set(
            jnp.arange(tokens * k, dtype=jnp.int32),
            unique_indices=True).reshape(tokens, k)
        row_of_choice = jnp.where(choice_is_held, row_of_choice, 0)
        row_is_routed = jnp.arange(rows, dtype=jnp.int32) < \
            jnp.sum(group_sizes)
        plan = (order[:rows], row_is_routed, row_of_choice, choice_is_held)
        buffer = _dispatch(x, plan)
    with jax.named_scope("moe.experts"):
        gate = grouped_matmul(buffer, w_gate, group_sizes)
        up = grouped_matmul(buffer, w_up, group_sizes)
        out = grouped_matmul(jax.nn.silu(gate) * up, w_down, group_sizes)
    with jax.named_scope("moe.combine"):
        return _combine(out, weights, plan)
