"""The language model of Keye-VL-2.0 (``model_type: KeyeVL2``), forward, loss
and gradients, written out plainly in float32 ``jax.numpy``: no model zoo, no
amp, no kernels, no radix search — a sort for the threshold, dense masks over
all experts, every held expert run on every token one after another, plain
``softmax`` over the selected keys.  The one copy: the tier-1 tests load this
file too (``tests/references/keye_vl2.py``).

A layer, from ISSUE 33's six steps (``h`` (T, hidden) one sequence's residual
stream, ``p`` (3, T) its positions: temporal, height, width):

1. ``x = RMSNorm(h)``; ``q = x Wq`` -> (H, T, d), ``k = x Wk``, ``v = x Wv``
   -> (Hkv, T, d); RMSNorm with a learned weight over the ``d`` of each head
   of q and of k (*assumed*: the lineage's published block); rotary in
   half-split pairs (``i`` with ``i + d/2``), the ``d/2`` frequencies
   ``theta^(-2i/d)`` divided among the three position streams by
   ``rope_scaling.mrope_section``.
2. The indexer, on ``stop_gradient(x)``: ``qI`` -> (HI, T, dI), ``kI`` -> (T,
   dI), ``w`` -> (T, HI); rotary over all of ``dI`` at the temporal position
   (*assumed*); ``I[t, s] = HI^-1/2 dI^-1/2 sum_j w[t, j] relu(qI[t, j] .
   kI[s])`` for ``s <= t``.
3. ``tau_t`` = the ``topk``-th largest of ``{I[t, s] : s <= t}`` (the
   smallest of them while ``t < topk``); ``S_t = {s <= t : I[t, s] >=
   tau_t}``: a tie takes both.
4. Head ``a`` reads key-value head ``a // (H / Hkv)``: ``o[t, a] =
   softmax_{s in S_t}(q[t, a] . k[s] / sqrt(d)) v[s]``; ``h' = h + concat(o)
   Wo``.
5. ``y = RMSNorm(h')``; ``g = softmax(y Wr)`` over all
   ``published.num_experts``; the ``num_experts_per_tok`` largest, divided by
   their sum; ``h'' = h' + sum_{e chosen and held} g_e E_e(y)``, ``E_e``
   SwiGLU.  The layer holds ``num_experts`` of them from ``expert_offset``
   on; a choice of an absent expert adds nothing.
6. The loss: mean next-token cross-entropy over every position over the
   vocabulary held, plus ``L_I = sum_layers mean_t KL(pbar_t || softmax_{S_t}
   I[t, .])``, ``pbar_t = mean_a P[t, a, .]`` without a gradient (*assumed*
   from DeepSeek Sparse Attention's sparse-training stage).  With ``x`` and
   ``pbar`` detached every parameter has its gradient from one term.

So that it fits the chip at the timed sizes, attention and ``L_I`` take
``QUERIES_A_BLOCK`` queries at a time (a block's 32 heads of scores are 32 x
128 x 16384 x 4 B = 0.27 GB), each block and each expert is a
``jax.checkpoint``, and :func:`gradient_program` chains the layers' vjps a
sequence and a layer at a time.  None of it changes a number.

:func:`control` gives the stand-ins the cell's limits are set against
(``runners/train_fused_grads.py``).  Parameters come in by the program's
names (``model_layer0_attn_q_proj_weight`` ...); ``sizes`` is the
configuration file.
"""
from __future__ import annotations

import contextlib
import functools

import jax
import jax.numpy as jnp

QUERIES_A_BLOCK = 128

# what control() changes while a function here is traced
_FLOAT8 = False
_DENSE_ATTENTION = False
_ROUTED_EXPERTS = True
_INDEX_LOSS = True


@contextlib.contextmanager
def control(name):
    """Trace the reference as one of its stand-ins, each of which a check
    has to refuse: ``"float8"``, every matmul's operands (weights,
    activations, attention probabilities, the indexer's queries and keys)
    rounded to ``float8_e4m3fn``, each scaled so that its largest element is
    the format's largest — the nearest precision below the bfloat16 the
    configuration states (products and sums stay float32, as on an MXU; the
    router, the index weights and the threshold stay float32, as in the
    program); ``"dense_attention"``, step 4 and ``L_I`` over the whole causal
    past; ``"no_experts"``, the experts' result left out of every layer;
    ``"no_index_loss"``, ``L_I`` left out."""
    global _FLOAT8, _DENSE_ATTENTION, _ROUTED_EXPERTS, _INDEX_LOSS
    before = _FLOAT8, _DENSE_ATTENTION, _ROUTED_EXPERTS, _INDEX_LOSS
    if name == "float8":
        _FLOAT8 = True
    elif name == "dense_attention":
        _DENSE_ATTENTION = True
    elif name == "no_experts":
        _ROUTED_EXPERTS = False
    elif name == "no_index_loss":
        _INDEX_LOSS = False
    else:
        raise ValueError(f"control {name!r}")
    try:
        yield
    finally:
        _FLOAT8, _DENSE_ATTENTION, _ROUTED_EXPERTS, _INDEX_LOSS = before


def operand(a):
    """A matmul's operand: itself, or under ``control("float8")`` rounded to
    that format after scaling the largest element to the format's largest
    (the gradient passes straight through)."""
    if not _FLOAT8:
        return a
    scale = float(jnp.finfo(jnp.float8_e4m3fn).max) \
        / jnp.maximum(jnp.max(jnp.abs(a)), 1e-30)
    low = (a * scale).astype(jnp.float8_e4m3fn).astype(a.dtype) / scale
    return a + jax.lax.stop_gradient(low - a)


def mm(a, b):
    return operand(a) @ operand(b)


def rms_norm(x, weight, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * weight


def rope_angles(positions, dim, theta, sections=None):
    """(T, dim / 2): a frequency's position times ``theta^(-2i / dim)``.
    ``positions`` (T,), or (3, T) with ``sections`` three counts adding up to
    ``dim / 2``: the first ``sections[0]`` frequencies read the first
    stream, the next ``sections[1]`` the second, the rest the third."""
    inv = theta ** (-jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    positions = positions.astype(jnp.float32)
    if sections is None:
        return positions[:, None] * inv[None]
    parts, first = [], 0
    for stream, count in enumerate(sections):
        parts.append(positions[stream][:, None] * inv[None, first:first + count])
        first += count
    return jnp.concatenate(parts, axis=-1)


def rope_half_split(u, angles):
    """Rotate the pairs (u[i], u[i + dim / 2]) by ``angles[..., i]``; ``u``
    is (..., T, dim), ``angles`` (T, dim / 2)."""
    half = u.shape[-1] // 2
    first, second = u[..., :half], u[..., half:]
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    return jnp.concatenate([first * cos - second * sin,
                            second * cos + first * sin], axis=-1)


def projections(own, x, positions, sizes):
    """Step 1: ``q (H, T, d)``, ``k`` and ``v (Hkv, T, d)``."""
    t = x.shape[0]
    h, hkv, d = sizes["num_attention_heads"], sizes["num_key_value_heads"], \
        sizes["head_dim"]
    eps = sizes["rms_norm_eps"]
    angles = rope_angles(positions, d, sizes["rope_theta"],
                         sizes["rope_scaling"]["mrope_section"])

    def heads(name, n):
        return mm(x, own[f"attn_{name}_proj_weight"].T).reshape(
            t, n, d).transpose(1, 0, 2)
    q = rope_half_split(rms_norm(heads("q", h), own["attn_q_norm_weight"],
                                 eps), angles)
    k = rope_half_split(rms_norm(heads("k", hkv), own["attn_k_norm_weight"],
                                 eps), angles)
    return q, k, heads("v", hkv)


def indexer(own, x, positions, sizes):
    """Step 2's operands from ``stop_gradient(x)``: ``qI (HI, T, dI)``, ``kI
    (T, dI)``, ``w (T, HI)``."""
    t = x.shape[0]
    sa = sizes["sa_config"]
    hi, di = sa["indexer_num_heads"], sa["indexer_head_dim"]
    x = jax.lax.stop_gradient(x)
    angles = rope_angles(positions[0], di, sizes["rope_theta"])
    qi = mm(x, own["indexer_wq_proj_weight"].T).reshape(
        t, hi, di).transpose(1, 0, 2)
    ki = mm(x, own["indexer_wk_proj_weight"].T)
    return rope_half_split(qi, angles), rope_half_split(ki, angles), \
        x @ own["indexer_weights_proj_weight"].T


def selection(scores, first, topk):
    """Step 3 for the queries ``first ..`` of ``scores`` (bq, T): (bq, T)
    bool."""
    bq, t = scores.shape
    position = first + jnp.arange(bq)
    causal = jnp.arange(t)[None] <= position[:, None]
    if _DENSE_ATTENTION:
        return causal
    ordered = -jnp.sort(-jnp.where(causal, scores, -jnp.inf), axis=-1)
    tau = jnp.take_along_axis(
        ordered, jnp.minimum(topk, position + 1)[:, None] - 1, axis=-1)
    return causal & (scores >= tau)


def attend(q, k, v, qi, ki, w, first, sizes):
    """Steps 2 to 4 and ``L_I``'s part for one block of queries: q (H, bq,
    d), k, v (Hkv, T, d), qi (HI, bq, dI), ki (T, dI), w (bq, HI) -> ``(o
    (bq, H d), sum over the block of KL_t)``."""
    h, bq, d = q.shape
    hkv, t, _ = k.shape
    sa = sizes["sa_config"]
    scale = sa["indexer_num_heads"] ** -0.5 * sa["indexer_head_dim"] ** -0.5
    pre = jnp.einsum("jqd,sd->jqs", operand(qi), operand(ki))
    scores = scale * jnp.einsum("jqs,qj->qs", jax.nn.relu(pre), w)
    chosen = selection(jax.lax.stop_gradient(scores), first, sa["topk"])

    grouped = q.reshape(hkv, h // hkv, bq, d)
    logits = jnp.einsum("grqd,gsd->grqs", operand(grouped), operand(k)) \
        * d ** -0.5
    p = jax.nn.softmax(jnp.where(chosen, logits, -jnp.inf), axis=-1)
    o = jnp.einsum("grqs,gsd->grqd", operand(p), operand(v))
    o = o.reshape(h, bq, d).transpose(1, 0, 2).reshape(bq, h * d)
    if not _INDEX_LOSS:
        return o, jnp.float32(0)
    pbar = jax.lax.stop_gradient(jnp.mean(p, axis=(0, 1)))         # (bq, T)
    log_index = jax.nn.log_softmax(
        jnp.where(chosen, scores, -jnp.inf), axis=-1)
    kl = jnp.where(pbar > 0, pbar * (jnp.log(jnp.where(pbar > 0, pbar, 1.0))
                                     - jnp.where(chosen, log_index, 0.0)),
                   0.0)
    return o, jnp.sum(kl)


def sparse_attention(own, x, positions, sizes, block=None):
    """``(concat(o) (T, H d), sum_t KL_t)`` of one sequence, a block of
    queries at a time (``lax.map`` runs them one after another)."""
    t = x.shape[0]
    block = min(block or QUERIES_A_BLOCK, t)
    if t % block:
        raise ValueError(f"{t} positions do not come in blocks of {block}")
    q, k, v = projections(own, x, positions, sizes)
    qi, ki, w = indexer(own, x, positions, sizes)

    def blocks(a, axis):
        shape = a.shape[:axis] + (t // block, block) + a.shape[axis + 1:]
        return jnp.moveaxis(a.reshape(shape), axis, 0)
    one = jax.checkpoint(functools.partial(attend, sizes=sizes))
    o, kl = jax.lax.map(
        lambda a: one(a[0], k, v, a[1], ki, a[2], a[3]),
        (blocks(q, 1), blocks(qi, 1), blocks(w, 0),
         jnp.arange(0, t, block)))
    return o.reshape(t, -1), jnp.sum(kl)


def router(own, y, sizes):
    """(T, E) combine weights: for the ``num_experts_per_tok`` largest of
    ``softmax(y Wr)`` the gate divided by the sum of the chosen; 0
    elsewhere."""
    gates = jax.nn.softmax(y @ own["moe_router_weight"].T, axis=-1)
    k = sizes["num_experts_per_tok"]
    kth = jnp.sort(gates, axis=-1)[..., -k][..., None]
    weights = jnp.where(gates >= kth, gates, 0.0)
    if sizes["norm_topk_prob"]:
        weights = weights / (jnp.sum(weights, -1, keepdims=True) + 1e-20)
    return weights


def held_range(sizes, expert_offset=None, held=None):
    offset = sizes.get("expert_offset", 0) if expert_offset is None \
        else expert_offset
    return offset, sizes["num_experts"] if held is None else held


def experts(own, y, sizes, expert_offset=None, held=None):
    """Step 5's sum for the experts held here: every held expert runs on
    every token, weighted by the router's table."""
    offset, held = held_range(sizes, expert_offset, held)
    weights = router(own, y, sizes)

    @jax.checkpoint
    def add_expert(out, expert):
        w, w_gate, w_up, w_down = expert
        return out + w[..., None] * mm(
            jax.nn.silu(mm(y, w_gate)) * mm(y, w_up), w_down), None
    out, _ = jax.lax.scan(add_expert, jnp.zeros_like(y), (
        jnp.moveaxis(weights[..., offset:offset + held], -1, 0),
        own["moe_experts_gate_weight"][:held],
        own["moe_experts_up_weight"][:held],
        own["moe_experts_down_weight"][:held]))
    return out


def layer_parameters(params, i):
    """Layer ``i``'s parameters under their names without the layer's own
    prefix: the same keys for every layer."""
    prefix = f"model_layer{i}_"
    return {k[len(prefix):]: v for k, v in params.items()
            if k.startswith(prefix)}


def layer(own, h, positions, sizes, block=None):
    """One layer on its :func:`layer_parameters`: ``(h'', sum_t KL_t,
    rows)``, rows the choices that landed on an expert held here."""
    eps = sizes["rms_norm_eps"]
    x = rms_norm(h, own["input_norm_weight"], eps)
    o, kl = sparse_attention(own, x, positions, sizes, block)
    h = h + mm(o, own["attn_o_proj_weight"].T)
    y = rms_norm(h, own["post_norm_weight"], eps)
    offset, held = held_range(sizes)
    chosen = router(own, y, sizes) > 0
    rows = jnp.sum(chosen[..., offset:offset + held], dtype=jnp.int32)
    if _ROUTED_EXPERTS:
        h = h + experts(own, y, sizes)
    return h, kl, rows


def next_token_nll(h, norm_weight, head_weight, wanted, sizes):
    """Sum over the positions of ``-log softmax(head(norm(h)))[wanted]``."""
    h = rms_norm(h, norm_weight, sizes["rms_norm_eps"])
    logp = jax.nn.log_softmax(mm(h, head_weight.T), axis=-1)
    return -jnp.sum(jnp.take_along_axis(
        logp, wanted.astype(jnp.int32)[..., None], axis=-1))


def text_positions(t):
    """For text the three streams coincide: the token's index."""
    return jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32), (3, t))


def forward(params, tokens, sizes, positions=None, block=None):
    """``(logits (B, T, V), index_loss (B,))`` as the network returns them:
    ``index_loss`` a sequence's ``sum_layers mean_t KL_t``.  ``positions``
    (3, B, T) or None for text."""
    with jax.default_matmul_precision("highest"):
        params = {k: v.astype(jnp.float32) for k, v in params.items()}
        logits, losses = [], []
        for b, ids in enumerate(tokens):
            t = ids.shape[0]
            p = text_positions(t) if positions is None else positions[:, b]
            h, total = params["model_embed_weight"][ids], 0.0
            for i in range(sizes["num_hidden_layers"]):
                h, kl, _ = layer(layer_parameters(params, i), h, p, sizes,
                                 block)
                total = total + kl / t
            h = rms_norm(h, params["model_norm_weight"],
                         sizes["rms_norm_eps"])
            logits.append(mm(h, params["lm_head_weight"].T))
            losses.append(total)
        return jnp.stack(logits), jnp.stack(losses)


def loss(params, batch, sizes, positions=None, block=None):
    """Step 6, as one differentiable function of the whole model (the tests'
    form; :func:`gradient_program` is what fits the chip)."""
    tokens, targets = batch
    logits, index_loss = forward(params, tokens, sizes, positions, block)
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, targets.astype(jnp.int32)[..., None],
                               axis=-1)
    return jnp.mean(nll) + jnp.mean(index_loss)


def gradient_program(sizes, watched, stand_in=None, block=None):
    """``run(params, batch, gradients=True) -> (loss, rows, {name:
    gradient})`` for the layers' parameters named in ``watched``: what
    ``jax.grad`` of :func:`loss` gives for them (a tier-1 test holds the two
    together), computed one sequence and one layer at a time by chaining the
    layers' vjps from the loss down, each layer handing down the cotangent of
    its input with its own ``L_I`` beside the cross-entropy's.  So the
    compiled programs are one layer's forward, one layer's backward and the
    head, each used again for every layer and every sequence.  ``stand_in``
    names a :func:`control` to trace under.  ``gradients=False`` stops after
    the loss and the rows.  Text positions."""
    kinds = {name: name.split("_", 2) for name in watched}
    if any(len(k) != 3 or not k[1].startswith("layer")
           for k in kinds.values()):
        raise ValueError(f"a layer's parameters only, not {sorted(watched)}")
    wanted_kinds = sorted({k[2] for k in kinds.values()})

    def traced(fn):
        def under(*args):
            with control(stand_in) if stand_in else contextlib.nullcontext(), \
                    jax.default_matmul_precision("highest"):
                return fn(*args)
        return jax.jit(under)

    def one_layer(own, h):
        return layer(own, h, text_positions(h.shape[0]), sizes, block)

    def layer_vjp(own, h, ct):
        # the loss is the cross-entropy's sum plus every layer's sum of
        # KL_t, both divided by the batch's positions at the end
        _, vjp = jax.vjp(lambda own, h: one_layer(own, h)[:2], own, h)
        g_own, g_h = vjp((ct, jnp.float32(1)))
        return g_h, {k: g_own[k] for k in wanted_kinds if k in g_own}
    forward_ = traced(one_layer)
    backward = traced(layer_vjp)
    nll_and_ct = traced(jax.value_and_grad(
        lambda h, norm, weight, wanted: next_token_nll(h, norm, weight,
                                                       wanted, sizes)))

    def run(params, batch, gradients=True):
        params = {k: v.astype(jnp.float32) for k, v in params.items()}
        tokens, targets = batch
        depth = sizes["num_hidden_layers"]
        total, rows, grads = 0.0, 0, {}
        for ids, wanted in zip(tokens, targets):
            hs, routed = [params["model_embed_weight"][ids]], []
            for i in range(depth):
                h, kl, r = forward_(layer_parameters(params, i), hs[-1])
                hs.append(h)
                routed.append(r)
                total = total + kl
            rows = rows + jnp.stack(routed)
            value, ct = nll_and_ct(hs.pop(), params["model_norm_weight"],
                                   params["lm_head_weight"], wanted)
            total = total + value
            for i in reversed(range(depth if gradients else 0)):
                ct, own = backward(layer_parameters(params, i), hs.pop(), ct)
                for kind, g in own.items():
                    name = f"model_layer{i}_{kind}"
                    if name in kinds:
                        grads[name] = grads.get(name, 0.0) + g
        return total / targets.size, rows, \
            {name: g / targets.size for name, g in grads.items()}
    return run
