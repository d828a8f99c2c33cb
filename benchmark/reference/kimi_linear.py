"""Kimi Linear (``model_type: kimi_linear``, arXiv:2510.26692), forward, loss
and gradients, written out plainly in float32 ``jax.numpy``: no model zoo, no
amp, no kernels, **no chunks** — Kimi Delta Attention is the per-token
recurrence under ``lax.scan``, latent attention plain ``softmax(QK^T)V``
without positions, the experts dense masks over all of them.  The one copy:
the tier-1 tests load this file too (``tests/references/kimi_linear.py``).

A layer, from ISSUE 35 §1 (``h`` (B, T, hidden) the residual stream, pre-norm,
RMSNorm eps ``rms_norm_eps``, no bias anywhere, no positional encoding):

1. A **KDA** layer (one that has an ``attn_A_log``), ``x = RMSNorm(h)``, ``H``
   heads of ``d``: ``q = L2Norm(SiLU(Conv(x Wq)))``, ``k`` likewise, ``v =
   SiLU(Conv(x Wv))`` — ``Conv`` a causal depthwise convolution over time (a
   weight a channel and tap: token ``t`` sees ``t - 3 .. t``, zeros before the
   start), the L2 norm over each head's ``d`` (``x / sqrt(sum x^2 + 1e-6)``),
   q then scaled by ``d^-1/2``; ``g = -exp(A_log) softplus(x Wf1 Wf2 +
   dt_bias)``, ``alpha = exp(g)`` a head, token and key channel; ``beta =
   sigmoid(x Wb)`` a head and token; from ``S_0 = 0``::

       S_t = (I - beta_t k_t k_t^T) Diag(alpha_t) S_{t-1} + beta_t k_t v_t^T
       o_t = S_t^T q_t

   ``h += Wo(sigmoid(x Wg1 Wg2) * RMSNorm_head(o))``, the norm over each
   head's ``d`` with one learned weight of ``d``.
2. An **MLA** layer: ``q = x Wq`` -> heads of ``nope + rope``; ``[c | k_r] = x
   Wkva``; ``c = RMSNorm(c)``; ``c Wkvb`` -> heads of ``[k_nope | v]``; causal
   ``softmax(q [k_nope | k_r]^T (nope + rope)^-1/2) v``; **no rotation**
   (``mla_use_nope``).
3. ``y = RMSNorm(h)``; the first layer ``h += SwiGLU(y)``; the others the
   expert layer of ``reference/deepseek_v3.py`` (sigmoid scores over all
   ``published.num_experts``, the top ``num_experts_per_tok`` of ``s + b``
   renormalised and scaled, the experts held here, one shared expert), which
   this file loads rather than writes again.

Departures from the source, all the configuration's: the share of the experts
and of the vocabulary; what ``config.json`` does not say (the configuration
file's ``assumed``) as the published reference implementation has it.

So that it fits the chip at the timed sizes the recurrence runs
up to ``TOKENS_A_BLOCK`` tokens to a ``jax.checkpoint`` (its backward keeps one
state a block and computes a block's tokens again; blocks are not chunks: the
mathematics inside is the token's), attention takes two heads at a time
(``ds.blocked_attention``), and :func:`gradient_program` chains the layers' vjps a sequence and a
layer at a time.  None of it changes a number.

:func:`control` gives the stand-ins the cell's limits are set against
(``runners/train_fused_grads.py``).  Parameters come in by the program's names
(``model_layer0_attn_v_proj_weight`` ...); ``sizes`` is the configuration
file.
"""
from __future__ import annotations

import contextlib
import functools
import importlib.util
import os

import jax
import jax.numpy as jnp

_spec = importlib.util.spec_from_file_location(
    __name__ + "_deepseek_v3",
    os.path.join(os.path.dirname(os.path.abspath(__file__)),
                 "deepseek_v3.py"))
ds = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(ds)

TOKENS_A_BLOCK = 128
# this file's own copy of the sibling takes two heads of scores at a time: at
# 8192 positions they are 2 x 8192 x 8192 x 4 B = 0.5 GB
ds.HEADS_A_BLOCK = 2

# what control() changes while a function here is traced
_DECAY = True
_DELTA = True

layer_parameters = ds.layer_parameters
router = ds.router
rms_norm = ds.rms_norm
mm = ds.mm


@contextlib.contextmanager
def control(name):
    """Trace the reference as one of its stand-ins, each of which a check has
    to refuse: ``"float8"`` and ``"no_experts"`` as
    ``reference/deepseek_v3.py`` has them (``"bfloat16"`` too, which has to
    pass); ``"no_decay"``, alpha = 1 (the plain delta rule); ``"no_delta"``,
    the ``-beta k k^T`` term dropped (gated linear attention)."""
    global _DECAY, _DELTA
    before = _DECAY, _DELTA
    if name == "no_decay":
        _DECAY = False
    elif name == "no_delta":
        _DELTA = False
    try:
        with ds.control(name) if name not in ("no_decay", "no_delta") \
                else contextlib.nullcontext():
            yield
    finally:
        _DECAY, _DELTA = before


def causal_conv(x, weight):
    """x (B, T, C), weight (C, K): ``y[t] = sum_i weight[:, i] x[t - K + 1 +
    i]``, zeros before the sequence starts."""
    taps, t = weight.shape[1], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
    return sum(padded[:, i:i + t] * weight[:, i] for i in range(taps))


def delta_rule(q, k, v, g, beta, state=None):
    """The recurrence, a token at a time.  q, k, g (B, T, H, dk), v (B, T, H,
    dv), beta (B, T, H) -> ``(o (B, T, H, dv), S_T (B, H, dk, dv))``."""
    b, t, h, dk = q.shape
    block = next(n for n in range(min(TOKENS_A_BLOCK, t), 0, -1)
                 if t % n == 0)

    def token(S, x):
        q_t, k_t, v_t, g_t, beta_t = x
        if _DECAY:
            S = jnp.exp(g_t)[..., None] * S
        write = v_t
        if _DELTA:
            write = v_t - jnp.einsum("bhk,bhkv->bhv", k_t, S)
        S = S + jnp.einsum("bhk,bhv->bhkv", k_t, beta_t[..., None] * write)
        return S, jnp.einsum("bhk,bhkv->bhv", q_t, S)

    @jax.checkpoint
    def tokens(S, xs):
        return jax.lax.scan(token, S, xs)

    def blocks(a):
        a = jnp.moveaxis(a, 1, 0)
        return a.reshape((t // block, block) + a.shape[1:])
    if state is None:
        state = jnp.zeros((b, h, dk, v.shape[-1]), jnp.float32)
    state, o = jax.lax.scan(tokens, state,
                            tuple(blocks(a) for a in (q, k, v, g, beta)))
    return jnp.moveaxis(o.reshape((t,) + o.shape[2:]), 0, 1), state


def kda_operands(own, x, sizes):
    """``q, k, v, g (B, T, H, d)`` and ``beta (B, T, H)`` of a KDA layer from
    its normalised input."""
    b, t, _ = x.shape
    lin = sizes["linear_attn_config"]
    h, d = lin["num_heads"], lin["head_dim"]

    def mixed(name):
        return jax.nn.silu(causal_conv(
            mm(x, own[f"attn_{name}_proj_weight"].T),
            own[f"attn_{name}_conv_weight"])).reshape(b, t, h, d)

    def unit(a):
        return a / jnp.sqrt(jnp.sum(a * a, axis=-1, keepdims=True) + 1e-6)
    f = mm(mm(x, own["attn_f_a_proj_weight"].T), own["attn_f_b_proj_weight"].T)
    g = -jnp.exp(own["attn_A_log"])[:, None] * jax.nn.softplus(
        (f + own["attn_dt_bias"]).reshape(b, t, h, d))
    beta = jax.nn.sigmoid(mm(x, own["attn_b_proj_weight"].T))
    return unit(mixed("q")) * d ** -0.5, unit(mixed("k")), mixed("v"), g, beta


def kda(own, x, sizes):
    """Step 1 on the layer's normalised input: (B, T, hidden)."""
    b, t, _ = x.shape
    q, k, v, g, beta = kda_operands(own, x, sizes)
    o, _ = delta_rule(ds.operand(q), ds.operand(k), ds.operand(v), g, beta)
    o = ds.stored(o)
    normed = rms_norm(o, own["attn_o_norm_weight"], sizes["rms_norm_eps"])
    gate = jax.nn.sigmoid(mm(mm(x, own["attn_g_a_proj_weight"].T),
                             own["attn_g_b_proj_weight"].T))
    return mm(normed.reshape(b, t, -1) * gate, own["attn_o_proj_weight"].T)


def mla_qkv(own, x, sizes, rotate=False):
    """Queries, keys and values of an MLA layer, (B, H, T, .).  ``rotate``
    gives kanana's rotated form (what ``mla_use_nope`` turns off), for the
    test that the two differ."""
    if rotate:
        return ds.mla_qkv(own, "attn_", x, sizes)
    b, t, _ = x.shape
    h = sizes["num_attention_heads"]
    nope, rope = sizes["qk_nope_head_dim"], sizes["qk_rope_head_dim"]
    dv, rank = sizes["v_head_dim"], sizes["kv_lora_rank"]
    q = mm(x, own["attn_q_proj_weight"].T).reshape(b, t, h, nope + rope)
    kva = mm(x, own["attn_kv_a_proj_weight"].T)
    latent = rms_norm(kva[..., :rank], own["attn_kv_a_norm_weight"],
                      sizes["rms_norm_eps"])
    kv = mm(latent, own["attn_kv_b_proj_weight"].T).reshape(
        b, t, h, nope + dv).transpose(0, 2, 1, 3)
    key = jnp.concatenate(
        [kv[..., :nope],
         jnp.broadcast_to(kva[:, None, :, rank:], (b, h, t, rope))], axis=-1)
    return q.transpose(0, 2, 1, 3), key, kv[..., nope:]


def mla(own, x, sizes, rotate=False):
    b, t, _ = x.shape
    out = ds.blocked_attention(*mla_qkv(own, x, sizes, rotate))
    return mm(out.transpose(0, 2, 1, 3).reshape(b, t, -1),
              own["attn_o_proj_weight"].T)


def layer(own, h, sizes):
    """One layer on its :func:`layer_parameters`: ``(h, rows)``, rows the
    choices that landed on an expert held here (0 for a dense layer)."""
    eps = sizes["rms_norm_eps"]
    x = rms_norm(h, own["input_norm_weight"], eps)
    mixer = kda if "attn_A_log" in own else mla
    h = ds.stored(h + mixer(own, x, sizes))
    y = rms_norm(h, own["post_norm_weight"], eps)
    if "mlp_dense0_weight" in own:
        return ds.stored(h + ds.swiglu(own, "mlp_", y)), jnp.int32(0)
    offset, held = ds.held_range(sizes)
    chosen = router(own, "moe_", y, sizes) > 0
    return ds.stored(h + ds.moe(own, "moe_", y, sizes)), \
        jnp.sum(chosen[..., offset:offset + held], dtype=jnp.int32)


def hidden_states(params, tokens, sizes):
    h = params["model_embed_weight"][tokens]
    rows = []
    for i in range(sizes["num_hidden_layers"]):
        h, routed = jax.checkpoint(functools.partial(layer, sizes=sizes))(
            layer_parameters(params, i), h)
        rows.append(routed)
    return h, jnp.stack(rows)


def logits(params, tokens, sizes):
    with jax.default_matmul_precision("highest"):
        params = {k: v.astype(jnp.float32) for k, v in params.items()}
        h, _ = hidden_states(params, tokens, sizes)
        h = rms_norm(h, params["model_norm_weight"], sizes["rms_norm_eps"])
        return mm(h, params["lm_head_weight"].T)


def loss(params, batch, sizes):
    """Mean next-token cross-entropy over every position of the batch, as one
    differentiable function of the whole model (the tests' form;
    :func:`gradient_program` is what fits the chip)."""
    tokens, targets = batch
    with jax.default_matmul_precision("highest"):
        params = {k: v.astype(jnp.float32) for k, v in params.items()}
        h, _ = hidden_states(params, tokens, sizes)
        return ds.next_token_nll(h, params["model_norm_weight"],
                                 params["lm_head_weight"], targets,
                                 sizes) / targets.size


def gradient_program(sizes, watched, stand_in=None):
    """``run(params, batch, gradients=True) -> (loss, rows, {name:
    gradient})`` for the layers' parameters named in ``watched``: what
    ``jax.grad`` of :func:`loss` gives for them (a tier-1 test holds the two
    together), one sequence and one layer at a time by chaining the layers'
    vjps from the loss down, as ``reference/deepseek_v3.py`` does and for its
    reasons: the compiled programs are a layer's forward and backward for
    each kind of layer and the head.  ``stand_in`` names a :func:`control`
    to trace under.  ``gradients=False`` stops after the loss and the
    rows."""
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

    def layer_vjp(own, h, ct):
        _, vjp = jax.vjp(lambda own, h: layer(own, h, sizes)[0], own, h)
        g_own, g_h = vjp(ct)
        return g_h, {k: g_own[k] for k in wanted_kinds if k in g_own}
    forward = traced(lambda own, h: layer(own, h, sizes))
    backward = traced(layer_vjp)
    nll_and_ct = traced(jax.value_and_grad(
        lambda h, norm, weight, wanted: ds.next_token_nll(
            h, norm, weight, wanted, sizes)))

    def run(params, batch, gradients=True):
        params = {k: v.astype(jnp.float32) for k, v in params.items()}
        tokens, targets = batch
        depth = sizes["num_hidden_layers"]
        nll, rows, grads = 0.0, 0, {}
        for ids, wanted in zip(tokens, targets):
            hs, routed = [params["model_embed_weight"][ids][None]], []
            for i in range(depth):
                h, r = forward(layer_parameters(params, i), hs[-1])
                hs.append(h)
                routed.append(r)
            rows = rows + jnp.stack(routed)
            value, ct = nll_and_ct(hs.pop(), params["model_norm_weight"],
                                   params["lm_head_weight"], wanted[None])
            nll = nll + value
            for i in reversed(range(depth if gradients else 0)):
                ct, own = backward(layer_parameters(params, i), hs.pop(), ct)
                for kind, g in own.items():
                    name = f"model_layer{i}_{kind}"
                    if name in kinds:
                        grads[name] = grads.get(name, 0.0) + g
        return nll / targets.size, rows, \
            {name: g / targets.size for name, g in grads.items()}
    return run
