"""SDAR's mixture-of-experts decoder (``model_type: sdar_moe``) under the
block-diffusion objective, forward, loss and gradients, written out plainly
in float32 ``jax.numpy``: no model zoo, no amp, no kernels — the published
mask over the whole training sequence, plain ``softmax``, dense masks over
all experts, every held expert run on every token one after another.  The
one copy: the tier-1 tests load this file too
(``tests/references/sdar_moe.py``).  What it shares with keye's reference
(the norms, rotary, the softmax router and the experts) it takes from that
file, loaded by its path.

A batch row is ``x_t ⊕ x_0`` (2T tokens: the noisy copy, then the clean
one) with a label (2, T): the clean ids and each position's weight, ``1 /
t`` of its block where it was masked, 0 where not.  A layer, ``h`` (2T,
hidden) one row's residual stream, ``blk(p) = p // block_length`` within a
half:

1. ``x = RMSNorm(h)``; ``q = x Wq`` -> (H, 2T, d), ``k = x Wk``, ``v = x
   Wv`` -> (Hkv, 2T, d); RMSNorm with a learned weight over each head of q
   and of k (*assumed*: the lineage's published block); rotary in
   half-split pairs over all of ``d`` at the position within the half (both
   halves 0 .. T - 1).
2. Head ``a`` reads kv head ``a // (H / Hkv)`` over the keys its query
   sees: a clean query the clean keys with ``blk(s) <= blk(t)``, a noisy
   query the clean keys with ``blk(s) < blk(t)`` and the noisy keys with
   ``blk(s) == blk(t)``; ``h' = h + concat(o) Wo``.
3. ``y = RMSNorm(h')``; ``g = softmax(y Wr)`` over all
   ``published.num_experts``; the ``num_experts_per_tok`` largest, divided
   by their sum; ``h'' = h' + sum_{e chosen and held} g_e E_e(y)``, ``E_e``
   SwiGLU; a choice of an absent expert adds nothing.
4. The loss: the head over the noisy half, ``(1 / (B T)) sum_p w_p
   CE(logits_p, x0_p)`` — position ``p`` predicts its own clean token.

So that it fits the chip at the timed sizes, attention takes
``QUERIES_A_BLOCK`` queries at a time (a block's 32 heads of scores over
16384 keys are 0.27 GB), each block and each expert is a
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
import importlib.util
import os

import jax
import jax.numpy as jnp

_spec = importlib.util.spec_from_file_location(
    __name__ + "_keye_vl2",
    os.path.join(os.path.dirname(os.path.abspath(__file__)), "keye_vl2.py"))
kv = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(kv)

QUERIES_A_BLOCK = 128

# what control() changes while a function here is traced
_MASK = "block_diffusion"
_ROUTED_EXPERTS = True

rms_norm = kv.rms_norm
layer_parameters = kv.layer_parameters
router = kv.router
experts = kv.experts
mm = kv.mm


@contextlib.contextmanager
def control(name):
    """Trace the reference as one of its stand-ins: ``"float8"``, every
    matmul's operands rounded to ``float8_e4m3fn`` after scaling the largest
    element to the format's largest (keye's reference's own: the nearest
    precision below the configuration's bfloat16); ``"causal"``, plain
    token-causal attention over the whole 2T sequence in place of the
    block-diffusion mask; ``"leak"``, a noisy query also sees the clean copy
    of its own block; ``"no_experts"``, the experts' result left out of
    every layer."""
    global _MASK, _ROUTED_EXPERTS
    before = _MASK, _ROUTED_EXPERTS
    inner = contextlib.nullcontext()
    if name == "float8":
        inner = kv.control("float8")
    elif name in ("causal", "leak"):
        _MASK = name
    elif name == "no_experts":
        _ROUTED_EXPERTS = False
    else:
        raise ValueError(f"control {name!r}")
    try:
        with inner:
            yield
    finally:
        _MASK, _ROUTED_EXPERTS = before


def visible(first, count, length, block_length):
    """(count, 2T) bool: which of the 2T keys the queries ``first ..
    first + count`` see (the noisy half first, positions counted within
    their half)."""
    a = first + jnp.arange(count)[:, None]
    b = jnp.arange(2 * length)[None, :]
    if _MASK == "causal":
        return b <= a
    noisy_q, noisy_k = a < length, b < length
    qb, kb = (a % length) // block_length, (b % length) // block_length
    before = kb <= qb if _MASK == "leak" else kb < qb
    return jnp.where(noisy_q, jnp.where(noisy_k, kb == qb, before),
                     ~noisy_k & (kb <= qb))


def projections(own, x, sizes):
    """Step 1: ``q (H, 2T, d)``, ``k`` and ``v (Hkv, 2T, d)``."""
    t2 = x.shape[0]
    h, hkv, d = sizes["num_attention_heads"], sizes["num_key_value_heads"], \
        sizes["head_dim"]
    eps = sizes["rms_norm_eps"]
    angles = kv.rope_angles(jnp.arange(t2) % (t2 // 2), d,
                            sizes["rope_theta"])

    def heads(name, n):
        return mm(x, own[f"attn_{name}_proj_weight"].T).reshape(
            t2, n, d).transpose(1, 0, 2)
    q = kv.rope_half_split(rms_norm(heads("q", h), own["attn_q_norm_weight"],
                                    eps), angles)
    k = kv.rope_half_split(rms_norm(heads("k", hkv),
                                    own["attn_k_norm_weight"], eps), angles)
    return q, k, heads("v", hkv)


def attend(q, k, v, first, sizes):
    """Step 2 for one block of queries: q (H, bq, d), k, v (Hkv, 2T, d) ->
    o (bq, H d)."""
    h, bq, d = q.shape
    hkv, t2, _ = k.shape
    seen = visible(first, bq, t2 // 2, sizes["block_length"])
    grouped = q.reshape(hkv, h // hkv, bq, d)
    logits = jnp.einsum("grqd,gsd->grqs", kv.operand(grouped),
                        kv.operand(k)) * d ** -0.5
    p = jax.nn.softmax(jnp.where(seen, logits, -jnp.inf), axis=-1)
    o = jnp.einsum("grqs,gsd->grqd", kv.operand(p), kv.operand(v))
    return o.reshape(h, bq, d).transpose(1, 0, 2).reshape(bq, h * d)


def attention(own, x, sizes, block=None):
    """``concat(o) (2T, H d)`` of one row, a block of queries at a time
    (``lax.map`` runs them one after another)."""
    t2 = x.shape[0]
    block = min(block or QUERIES_A_BLOCK, t2)
    if t2 % block:
        raise ValueError(f"{t2} positions do not come in blocks of {block}")
    q, k, v = projections(own, x, sizes)
    one = jax.checkpoint(functools.partial(attend, sizes=sizes))
    o = jax.lax.map(lambda a: one(a[0], k, v, a[1]),
                    (jnp.moveaxis(q.reshape(q.shape[0], t2 // block, block,
                                            -1), 1, 0),
                     jnp.arange(0, t2, block)))
    return o.reshape(t2, -1)


def layer(own, h, sizes, block=None):
    """One layer on its :func:`layer_parameters`: ``(h'', rows)``, rows
    the choices that landed on an expert held here."""
    eps = sizes["rms_norm_eps"]
    x = rms_norm(h, own["input_norm_weight"], eps)
    h = h + mm(attention(own, x, sizes, block), own["attn_o_proj_weight"].T)
    y = rms_norm(h, own["post_norm_weight"], eps)
    offset, held = kv.held_range(sizes)
    chosen = router(own, y, sizes) > 0
    rows = jnp.sum(chosen[..., offset:offset + held], dtype=jnp.int32)
    if _ROUTED_EXPERTS:
        h = h + experts(own, y, sizes)
    return h, rows


def weighted_nll(h, norm_weight, head_weight, label, sizes):
    """``sum_p w_p CE(head(norm(h_p)), x0_p)`` over the noisy half ``h`` (T,
    hidden); ``label`` (2, T): the clean ids and the weights."""
    h = rms_norm(h, norm_weight, sizes["rms_norm_eps"])
    logp = jax.nn.log_softmax(mm(h, head_weight.T), axis=-1)
    nll = -jnp.take_along_axis(logp, label[0].astype(jnp.int32)[:, None],
                               axis=-1)[:, 0]
    return jnp.sum(nll * label[1])


def loss(params, batch, sizes, block=None):
    """Step 4 as one differentiable function of the whole model (the tests'
    form; :func:`gradient_program` is what fits the chip)."""
    tokens, labels = batch
    with jax.default_matmul_precision("highest"):
        params = {k: v.astype(jnp.float32) for k, v in params.items()}
        total = 0.0
        for ids, label in zip(tokens, labels):
            h = params["model_embed_weight"][ids]
            for i in range(sizes["num_hidden_layers"]):
                h, _ = layer(layer_parameters(params, i), h, sizes, block)
            total = total + weighted_nll(
                h[:ids.shape[0] // 2], params["model_norm_weight"],
                params["lm_head_weight"], label, sizes)
        return total / labels[:, 0].size


def gradient_program(sizes, watched, stand_in=None, block=None):
    """``run(params, batch, gradients=True) -> (loss, rows, {name:
    gradient})`` for the parameters named in ``watched`` (a layer's, or
    ``model_embed_weight``): what ``jax.grad`` of :func:`loss` gives for
    them (a tier-1 test holds the two together), computed one sequence and
    one layer at a time by chaining the layers' vjps from the loss down;
    the embedding's gradient is the cotangent that reaches layer 0, summed
    into the rows of the ids that took it.  So the compiled programs are
    one layer's forward, one layer's backward and the head, each used again
    for every layer and every sequence.  ``stand_in`` names a
    :func:`control` to trace under.  ``gradients=False`` stops after the
    loss and the rows."""
    embed = "model_embed_weight"
    kinds = {name: name.split("_", 2) for name in watched if name != embed}
    if any(len(k) != 3 or not k[1].startswith("layer")
           for k in kinds.values()):
        raise ValueError(f"a layer's parameters or {embed} only, not "
                         f"{sorted(watched)}")
    wanted_kinds = sorted({k[2] for k in kinds.values()})

    def traced(fn):
        def under(*args):
            with control(stand_in) if stand_in else contextlib.nullcontext(), \
                    jax.default_matmul_precision("highest"):
                return fn(*args)
        return jax.jit(under)

    def one_layer(own, h):
        return layer(own, h, sizes, block)

    def layer_vjp(own, h, ct):
        _, vjp = jax.vjp(lambda own, h: one_layer(own, h)[0], own, h)
        g_own, g_h = vjp(ct)
        return g_h, {k: g_own[k] for k in wanted_kinds if k in g_own}

    def head(h, norm, weight, label):
        # the noisy half's loss; the clean half's cotangent is zero
        t = h.shape[0] // 2
        return weighted_nll(h[:t], norm, weight, label, sizes)
    forward_ = traced(one_layer)
    backward = traced(layer_vjp)
    nll_and_ct = traced(jax.value_and_grad(head))

    def run(params, batch, gradients=True):
        params = {k: v.astype(jnp.float32) for k, v in params.items()}
        tokens, labels = batch
        depth = sizes["num_hidden_layers"]
        total, rows, grads = 0.0, 0, {}
        for ids, label in zip(tokens, labels):
            hs, routed = [params[embed][ids]], []
            for i in range(depth):
                h, r = forward_(layer_parameters(params, i), hs[-1])
                hs.append(h)
                routed.append(r)
            rows = rows + jnp.stack(routed)
            value, ct = nll_and_ct(hs.pop(), params["model_norm_weight"],
                                   params["lm_head_weight"], label)
            total = total + value
            for i in reversed(range(depth if gradients else 0)):
                ct, own = backward(layer_parameters(params, i), hs.pop(), ct)
                for kind, g in own.items():
                    name = f"model_layer{i}_{kind}"
                    if name in kinds:
                        grads[name] = grads.get(name, 0.0) + g
            if gradients and embed in watched:
                grads[embed] = grads.get(embed, 0.0) + jnp.zeros_like(
                    params[embed]).at[ids].add(ct)
        n = labels[:, 0].size
        return total / n, rows, {name: g / n for name, g in grads.items()}
    return run
