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
   No capacity, no drop: assignments are sorted by expert and the rows of
   held experts gathered into a buffer for the expert products over
   ``group_sizes`` (``ops.grouped_matmul``), which do work for the rows
   that are routed.  Everything around the products is XLA over static
   shapes and pays for the buffer, so the buffer follows the routed count:
   the worst case (``tokens * min(top_k, held)`` rows, ``buffer_rows``) is
   4 to 32 times what a step routes, and a buffer has a quarter of its
   rows.  The sort and the index plan are made once, ``T * k`` scalars;
   the gather, the three products, the activation and the weighted sum
   back to tokens are one function of a buffer's rows, and a loop whose
   trip count the device reads from the routed count runs it over as many
   buffers (``window_rows``) as the rows need (``rung_rows``) — one in
   most steps, four at the worst case, float32 sums between them: no
   routing is dropped or clipped at any fill, and no array of the layer
   has the worst case's rows.  The token-side sums (the combine, and the
   dispatch's transpose) read each token's routed rows where they lie in
   the buffer (``ops.moe_sum_rows``): no copy of the buffer in token order.
   The loops are not differentiated through (their trip count is data):
   the layer is one custom VJP that keeps its operands alone, with a loop
   of its own in each rule; the backward rule's loop computes each buffer
   again and transposes that.  Under a decoder's per-layer ``remat`` this
   is what ran before (12 grouped products a layer and buffer: the
   recomputed forward loop has no reader and is dropped); a model without
   ``remat`` pays the three forward products a second time and holds none
   of the layer's buffers between the passes.
   On one chip it runs without its exchange; nothing here stands in for
   absent chips.  The Gluon block around it is
   ``gluon.model_zoo.nlp.decoder.MoEBlock``.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from .. import telemetry as _telem
from ..base import MXNetError

__all__ = ["moe_apply", "MoEDense", "load_balance_loss",
           "route_sigmoid_top_k", "route_softmax_top_k",
           "dropless_moe_apply", "buffer_rows", "window_rows", "rung_rows"]


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
    """Rows of the dispatch buffer's worst case, every token sending all it
    can (it chooses ``top_k`` distinct experts) to experts held here."""
    return tokens * min(top_k, held)


_TILE = 128                     # rows of the grouped product's tile
_PART = 4                       # buffers to the worst case


def window_rows(tokens, top_k, held, rows=None):
    """Rows of one buffer: ``rows`` where given, else a quarter of the worst
    case, in whole tiles of the grouped product (the worst case itself where
    that is smaller)."""
    full = buffer_rows(tokens, top_k, held)
    want = -(-full // _PART) if rows is None else rows
    return min(full, -(-want // _TILE) * _TILE)


def rung_rows(routed, tokens, top_k, held, rows=None):
    """The buffer rows a step computes over when ``routed`` rows go to held
    experts: whole buffers of :func:`window_rows`, as many as take them all
    (no count needs more than the worst case and a buffer's rounding)."""
    rows = window_rows(tokens, top_k, held, rows)
    return -(-routed // rows) * rows


class _Plan(NamedTuple):
    """One layer's routing as index arrays (no gradient), made once outside
    the loop over buffers: ``T * k`` scalars each."""
    choice_of_row: jax.Array    # (T * k,) the flat choice t * k + j in each
    #                             row: sorted by expert, then token
    row_of_choice: jax.Array    # (T, k) where each choice landed
    choice_is_held: jax.Array   # (T, k)
    group_sizes: jax.Array      # (held,) rows of each held expert
    routed: jax.Array           # () their sum


class _Window(NamedTuple):
    """``rows`` consecutive rows of the sorted choices, from ``start``: what
    one pass through the experts works on."""
    choice_of_row: jax.Array    # (rows,)
    row_is_routed: jax.Array    # (rows,)
    row_of_choice: jax.Array    # (T, k) counted from ``start``
    choice_is_here: jax.Array   # (T, k)
    group_sizes: jax.Array      # (held,) the rows of each expert in here

    @property
    def top_k(self):
        return self.row_of_choice.shape[1]


def _window(plan, rows, start):
    row_of_choice = plan.row_of_choice - start
    here = plan.choice_is_held & (row_of_choice >= 0) & (row_of_choice < rows)
    ends = jnp.cumsum(plan.group_sizes)
    return _Window(
        # (the last window may reach past the last choice, and dynamic_slice
        # would move it back)
        lax.dynamic_slice(jnp.pad(plan.choice_of_row, (0, rows)),
                          (start,), (rows,)),
        start + jnp.arange(rows, dtype=jnp.int32) < plan.routed,
        jnp.where(here, row_of_choice, 0), here,
        jnp.clip(ends, start, start + rows)
        - jnp.clip(ends - plan.group_sizes, start, start + rows))


def _gather_rows(v, window):
    """``rows[r] = v[token of the choice in row r]`` for the routed rows,
    zeros past them."""
    return jnp.where(window.row_is_routed[:, None],
                     v[window.choice_of_row // window.top_k], 0)


def _sum_rows(rows, window, scale=None):
    """``out[t] = sum_j scale[t, j] * rows[row_of_choice[t, j]]`` over the
    choices of token ``t`` in the window, float32, from 0 and in ascending
    ``j``; zeros for a token with no choice in the window.  Each routed row
    is read once where it lies (``ops.moe_sum_rows``)."""
    from ..ops.moe_sum_rows import sum_rows
    return sum_rows(rows, window.row_of_choice, window.choice_is_here, scale,
                    jnp.sum(window.row_is_routed, dtype=jnp.int32))


@jax.custom_vjp
def _dispatch(x, window):
    """:func:`_gather_rows`; its transpose is :func:`_sum_rows` (a row
    belongs to exactly one choice of one token), written as the backward
    rule: the scatter-add of rows of d that autodiff derives from a gather
    is not."""
    return _gather_rows(x, window)


_dispatch.defvjp(lambda x, window: (_gather_rows(x, window), window),
                 lambda window, g: (_sum_rows(g, window).astype(g.dtype),
                                    None))


@jax.custom_vjp
def _combine(buffer, weights, window):
    """``out[t] = sum_j weights[t, j] * buffer[row_of_choice[t, j]]`` over
    the choices in the window, float32."""
    return _sum_rows(buffer, window, weights.astype(jnp.float32))


def _combine_fwd(buffer, weights, window):
    return _combine(buffer, weights, window), (buffer, weights, window)


def _combine_bwd(res, g):
    buffer, weights, window = res
    # (g is the float32 copy of a cotangent that arrived in the buffer's
    # dtype: gathering it there moves half the bytes and loses nothing)
    g_of_row = _gather_rows(g.astype(buffer.dtype), window
                            ).astype(jnp.float32)
    scale = weights.reshape(-1)[window.choice_of_row].astype(jnp.float32)
    dbuffer = (g_of_row * scale[:, None]).astype(buffer.dtype)
    # a row-side reduce, and scalars back to (T, k)
    dscale = jnp.sum(buffer.astype(jnp.float32) * g_of_row, axis=-1)
    dweights = jnp.where(window.choice_is_here,
                         dscale[window.row_of_choice], 0)
    return dbuffer, dweights.astype(weights.dtype), None


_combine.defvjp(_combine_fwd, _combine_bwd)


def _experts_at(rows, start, x, weights, w_gate, w_up, w_down, plan,
                whole=False):
    """The routed experts over ``rows`` rows (static) of the sorted choices
    from ``start``: every array in here that is d or h wide has ``rows``
    rows or T.  (T, d) float32, the window's part of every token's sum.
    ``whole``: the products run over every row of the buffer, the last
    expert's group taking the zero rows past the routed ones."""
    from ..ops.grouped_matmul import grouped_matmul
    with jax.named_scope(f"moe.rung{rows}"):
        window = _window(plan, rows, start)
        sizes = window.group_sizes
        if whole:
            sizes = sizes.at[-1].add(rows - jnp.sum(sizes))
        with jax.named_scope("moe.dispatch"):
            buffer = _dispatch(x, window)
        with jax.named_scope("moe.experts"):
            gate = grouped_matmul(buffer, w_gate, sizes)
            up = grouped_matmul(buffer, w_up, sizes)
            out = grouped_matmul(jax.nn.silu(gate) * up, w_down, sizes)
        with jax.named_scope("moe.combine"):
            return _combine(out, weights, window)


def _over_windows(plan, rows, body, sums):
    """``sums`` plus ``body(start)`` for every buffer of ``rows`` rows that
    holds routed rows: a loop whose trip count the device reads.  Each sum
    is taken in float32 and kept in the dtype it came in."""
    def add(total, part):
        return (total.astype(jnp.float32) + part.astype(jnp.float32)
                ).astype(total.dtype)

    return lax.fori_loop(
        0, -(-plan.routed // rows),
        lambda i, sums: jax.tree.map(add, sums, body(i * rows)), sums)


# Both directions are jitted so that a model's layers share one trace (a
# ``pallas_call`` traces its kernel anew every time it is called).
# ``kernels`` is the cache's key for what ``ops.kernel_mode`` said when the
# trace was made.

@functools.partial(jax.jit, static_argnames=("rows", "whole", "kernels"))
def _forward(x, weights, w_gate, w_up, w_down, plan, *, rows, whole,
             kernels):
    return _over_windows(
        plan, rows,
        lambda start: _experts_at(rows, start, x, weights, w_gate, w_up,
                                  w_down, plan, whole),
        jnp.zeros(x.shape, jnp.float32)).astype(x.dtype)


@functools.partial(jax.jit, static_argnames=("rows", "whole", "kernels"))
def _backward(g, *operands, rows, whole, kernels):
    *floats, plan = operands
    x, weights, *stacks = floats

    def body(start):
        _, vjp = jax.vjp(lambda *floats: _experts_at(
            rows, start, *floats, plan, whole), *floats)
        return list(vjp(g.astype(jnp.float32)))

    # The token-side sums run in float32 from buffer to buffer.  The three
    # weight gradients stay in their own dtype: an expert's rows are
    # neighbours, so all of them but the one a buffer's edge cuts get their
    # whole gradient from one buffer (which adds to zeros, exactly), a
    # float32 copy of the three would be the layer's largest arrays (0.45 GB
    # of the kanana step), and a step whose rows fit one buffer — every
    # step of the benchmark's kimi and kanana cells — has no such expert.
    sums = _over_windows(
        plan, rows, body,
        [jnp.zeros(x.shape, jnp.float32), jnp.zeros_like(weights)]
        + [jnp.zeros_like(w) for w in stacks])
    return tuple(d.astype(f.dtype) for d, f in zip(sums, floats))


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _routed_experts(rows, whole, x, weights, w_gate, w_up, w_down, plan):
    """The sum of :func:`_experts_at` over the buffers of ``rows`` rows the
    routed count needs, one loop a direction.  Neither loop is
    differentiated (its trip count is data): the forward rule keeps the
    operands alone, and the backward rule's loop computes each buffer again
    and applies its VJP, with float32 sums between buffers in both."""
    from ..ops.kernel_mode import kernel_mode
    return _forward(x, weights, w_gate, w_up, w_down, plan, rows=rows,
                    whole=whole, kernels=kernel_mode())


def _routed_experts_fwd(rows, whole, *operands):
    return _routed_experts(rows, whole, *operands), operands


def _routed_experts_bwd(rows, whole, operands, g):
    from ..ops.kernel_mode import kernel_mode
    return (*_backward(g, *operands, rows=rows, whole=whole,
                       kernels=kernel_mode()), None)


_routed_experts.defvjp(_routed_experts_fwd, _routed_experts_bwd)


def _make_plan(experts, held, expert_offset):
    """The :class:`_Plan` of a layer that holds experts ``expert_offset ..
    expert_offset + held``: one sort of the ``T * k`` choices by expert and
    its inverse."""
    tokens, k = experts.shape
    with jax.named_scope("moe.plan"):
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
        return _Plan(order, row_of_choice, choice_is_held, group_sizes,
                     jnp.sum(group_sizes))


def dropless_moe_apply(x, experts, weights, w_gate, w_up, w_down, *,
                       expert_offset=0, fixed_rows=None):
    """The routed experts' part of a SwiGLU expert layer, for the experts
    held here.

    x: (T, d); experts: (T, k) int32 ids over all routed experts; weights:
    (T, k); w_gate, w_up: (held, d, h); w_down: (held, h, d), the weights of
    experts ``expert_offset .. expert_offset + held``.  Returns (T, d):
    ``sum_{j: experts[t, j] held} weights[t, j] * E_j(x[t])``.  A choice of
    an absent expert adds nothing; no choice of a held expert is dropped.

    The sort and the index plan are made once; the gather, the three
    grouped products, the activation and the sum back to tokens run through
    buffers of a quarter of the worst case's rows, as many as the rows
    routed in this step need (:func:`rung_rows`; counted on the device, no
    host sync, and at the worst case every choice of every token still
    fits).  The layer is one custom VJP that keeps its operands and nothing
    else, and its backward rule computes each buffer's forward again before
    transposing it.  Under a decoder's per-layer ``remat`` that is what
    happened anyway (the recomputed forward pass now has no reader and the
    compiler drops it: 12 grouped products a layer, as before); a model
    without ``remat`` runs the three forward products a second time in its
    backward pass and holds none of the layer's buffers in between.

    ``fixed_rows`` makes the work a fixed amount: buffers of that many rows
    (whole tiles, at most the worst case) in place of the quarter, and the
    products over every row of each, the rows past the routed ones as
    zeros.  A step whose routed rows fit one buffer then does the same work
    whatever the routing, at the price of the products over those zeros.
    """
    tokens, k = experts.shape
    held = w_gate.shape[0]
    rows = window_rows(tokens, k, held, fixed_rows)
    _telem.inc("moe.layers")
    _telem.set_gauge("moe.experts_held", held)
    _telem.set_gauge("moe.rows_buffer", buffer_rows(tokens, k, held))
    _telem.set_gauge("moe.rows_ladder", rows)
    _telem.set_gauge("moe.top_k", k)
    return _routed_experts(rows, fixed_rows is not None, x, weights, w_gate,
                           w_up, w_down,
                           _make_plan(experts, held, expert_offset))
