"""DeepSeek-V3-style decoder (``model_type: deepseek_v3``), forward and loss,
written out plainly in float32 ``jax.numpy``: no model zoo, no amp, no
kernels, no sorting — dense masks over all experts, every held expert run on
every token one after another, plain ``softmax(QK^T)V``.  Gradients are ``jax.grad`` of
:func:`loss`.  The one copy: the tier-1 tests load this file too
(``tests/references/deepseek_v3.py``) and give it ``causal_attention``.

So that the check fits the chip at the timed sizes and stays under the
step's own peak, which ``hbm_peak_gb`` reports, :func:`blocked_attention`
takes one block of heads at a time (a block's scores are 4 x 4096 x 4096 x
4 B = 0.27 GB), each layer, block of heads and expert is a
``jax.checkpoint`` (the backward pass computes it again rather than keep its
interior), and :func:`gradient_program` takes the gradients one sequence and
one layer at a time.  None of it changes a number.

It follows the DeepSeek-V3 report (arXiv:2412.19437, section 2.1) and the
published ``config.json`` of kakaocorp/kanana-2-30b-a3b-instruct-2601:
multi-head latent attention without a query latent (``q_lora_rank`` null), a
leading dense SwiGLU layer, then expert layers with a sigmoid router, a
selection bias, normalised and scaled top-k weights, and shared experts.
Departures, all the configuration's: the expert layer holds
``n_routed_experts`` of the ``published.n_routed_experts`` the router scores
(experts ``expert_offset`` onward; a choice of an absent expert adds
nothing), and the vocabulary is the slice held.  RoPE rotates interleaved
pairs in place where Hugging Face de-interleaves and rotates halves
(:func:`rope_permute_then_rotate_halves`): the same permutation of q and k,
so no score changes.

:func:`control` gives the stand-ins that the cell's limits are set against
(``runners/train_fused_grads.py``): the same model with every matmul's
operands rounded to float8 or to bfloat16, and with the routed experts left
out.

Parameters come in by the program's names (``model_layer1_moe_router_weight``
...); ``sizes`` is the configuration file.
"""
from __future__ import annotations

import contextlib
import functools

import jax
import jax.numpy as jnp

# what control() changes while a function here is traced
_ROUND_OPERANDS_TO = None
_ROUTED_EXPERTS = True


@contextlib.contextmanager
def control(name):
    """Trace the reference as one of its stand-ins.  Two that a check has to
    refuse: ``"float8"``, every matmul's operands (weights, activations,
    attention probabilities) rounded to ``float8_e4m3fn``, each scaled so
    that its largest element is the format's largest, the nearest precision
    below the bfloat16 the configuration states (products and sums stay
    float32, as on an MXU; the router stays float32, as in the program);
    ``"no_experts"``, the routed experts' result left out of every expert
    layer.  And one it has to pass, ``"bfloat16"``: the configuration's own
    precision as ``amp`` applies it, matmul operands rounded to bfloat16 and
    every matmul's result, attention's output and the residual stream
    *stored* in it, cotangents too (:func:`stored`), which shows how much of
    the program's reading is that precision (and the top-k choices it
    flips)."""
    global _ROUND_OPERANDS_TO, _ROUTED_EXPERTS
    before = _ROUND_OPERANDS_TO, _ROUTED_EXPERTS
    if name == "no_experts":
        _ROUTED_EXPERTS = False
    elif name in ("float8", "bfloat16"):
        _ROUND_OPERANDS_TO = {"float8": jnp.float8_e4m3fn,
                              "bfloat16": jnp.bfloat16}[name]
    else:
        raise ValueError(f"control {name!r}")
    try:
        yield
    finally:
        _ROUND_OPERANDS_TO, _ROUTED_EXPERTS = before


def operand(a):
    """A matmul's operand: itself, or under a rounding :func:`control`
    rounded to that format (the gradient passes straight through); float8
    after scaling the largest element to the format's largest."""
    if _ROUND_OPERANDS_TO is None:
        return a
    scale = 1.0
    if _ROUND_OPERANDS_TO == jnp.float8_e4m3fn:
        scale = float(jnp.finfo(_ROUND_OPERANDS_TO).max) \
            / jnp.maximum(jnp.max(jnp.abs(a)), 1e-30)
    low = (a * scale).astype(_ROUND_OPERANDS_TO).astype(a.dtype) / scale
    return a + jax.lax.stop_gradient(low - a)


@jax.custom_vjp
def _through_bfloat16(a):
    return a.astype(jnp.bfloat16).astype(a.dtype)


_through_bfloat16.defvjp(lambda a: (_through_bfloat16(a), None),
                         lambda _, g: (_through_bfloat16(g),))


def stored(a):
    """What a matmul, attention or a residual sum leaves behind: itself, or
    under ``control("bfloat16")`` rounded to bfloat16 on the way forward and
    its cotangent on the way back, as a program under ``amp`` stores it."""
    return _through_bfloat16(a) if _ROUND_OPERANDS_TO == jnp.bfloat16 else a


def mm(a, b):
    return stored(operand(a) @ operand(b))


def rms_norm(x, weight, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * weight


def swiglu(params, name, x):
    """``LlamaMLP``'s three matrices: dense0 gate, dense1 up, dense2 down."""
    gate = mm(x, params[name + "dense0_weight"].T)
    up = mm(x, params[name + "dense1_weight"].T)
    return mm(jax.nn.silu(gate) * up, params[name + "dense2_weight"].T)


def rope_angles(positions, dim, theta):
    """(T, dim / 2): position times theta^(-2i / dim)."""
    inv = theta ** (-jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    return positions.astype(jnp.float32)[:, None] * inv[None]


def rope_interleaved(u, angles):
    """Rotate the pairs (u[2i], u[2i + 1]) by ``angles[..., i]``; ``u`` is
    (..., T, dim), ``angles`` (T, dim / 2)."""
    even, odd = u[..., 0::2], u[..., 1::2]
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    return jnp.stack([even * cos - odd * sin, odd * cos + even * sin],
                     axis=-1).reshape(u.shape)


def rope_permute_then_rotate_halves(u, angles):
    """Hugging Face's ``apply_rotary_pos_emb_interleave``: bring the even
    dims to the first half and the odd ones to the second, then
    ``u cos + rotate_half(u) sin`` with the angles repeated for both
    halves."""
    u = jnp.concatenate([u[..., 0::2], u[..., 1::2]], axis=-1)
    half = u.shape[-1] // 2
    rotated = jnp.concatenate([-u[..., half:], u[..., :half]], axis=-1)
    cos = jnp.concatenate([jnp.cos(angles)] * 2, axis=-1)
    sin = jnp.concatenate([jnp.sin(angles)] * 2, axis=-1)
    return u * cos + rotated * sin


def mla_qkv(params, name, x, sizes):
    """Queries, keys and values of one layer, (B, H, T, .): q and k at
    ``nope + rope``, v at ``v_head_dim``."""
    b, t, _ = x.shape
    h = sizes["num_attention_heads"]
    nope, rope = sizes["qk_nope_head_dim"], sizes["qk_rope_head_dim"]
    dv, rank = sizes["v_head_dim"], sizes["kv_lora_rank"]
    q = mm(x, params[name + "q_proj_weight"].T).reshape(b, t, h, nope + rope)
    kva = mm(x, params[name + "kv_a_proj_weight"].T)
    latent = rms_norm(kva[..., :rank], params[name + "kv_a_norm_weight"],
                      sizes["rms_norm_eps"])
    kv = mm(latent, params[name + "kv_b_proj_weight"].T).reshape(
        b, t, h, nope + dv)
    angles = rope_angles(jnp.arange(t), rope, sizes["rope_theta"])
    q = q.transpose(0, 2, 1, 3)
    kv = kv.transpose(0, 2, 1, 3)
    q_pe = rope_interleaved(q[..., nope:], angles)
    k_pe = rope_interleaved(kva[:, None, :, rank:], angles)   # (B, 1, T, r)
    query = jnp.concatenate([q[..., :nope], q_pe], axis=-1)
    key = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_pe, (b, h, t, rope))], axis=-1)
    return query, key, kv[..., nope:]


def causal_attention(q, k, v):
    """(B, H, T, d), (B, H, T, d), (B, H, T, dv) -> (B, H, T, dv)."""
    t = q.shape[2]
    scores = jnp.einsum("bhqd,bhkd->bhqk", operand(q), operand(k)) \
        * q.shape[-1] ** -0.5
    scores = jnp.where(jnp.tril(jnp.ones((t, t), bool)), scores, -jnp.inf)
    return stored(jnp.einsum(
        "bhqk,bhkd->bhqd", operand(jax.nn.softmax(scores, axis=-1)),
        operand(v)))


HEADS_A_BLOCK = 4


def blocked_attention(q, k, v):
    """:func:`causal_attention`, one sequence and ``HEADS_A_BLOCK`` heads at
    a time (``lax.map`` runs the blocks one after another)."""
    b, h, t, _ = q.shape
    block = min(HEADS_A_BLOCK, h)
    if h % block:
        raise ValueError(f"{h} heads do not come in blocks of {block}")

    def blocks(a):
        return a.reshape(b * (h // block), 1, block, t, a.shape[-1])
    out = jax.lax.map(lambda qkv: jax.checkpoint(causal_attention)(*qkv),
                      (blocks(q), blocks(k), blocks(v)))
    return out.reshape(b, h, t, v.shape[-1])


def mla(params, name, x, sizes, attention=blocked_attention):
    b, t, _ = x.shape
    out = attention(*mla_qkv(params, name, x, sizes))
    return mm(out.transpose(0, 2, 1, 3).reshape(b, t, -1),
              params[name + "o_proj_weight"].T)


def router(params, name, y, sizes):
    """(..., E) combine weights: for the ``num_experts_per_tok`` largest of
    ``sigmoid(y W_g) + b`` the unbiased score, normalised over the chosen and
    scaled; 0 elsewhere."""
    scores = jax.nn.sigmoid(y @ params[name + "router_weight"].T)
    biased = scores + params[name + "e_score_correction_bias"]
    k = sizes["num_experts_per_tok"]
    kth = jnp.sort(biased, axis=-1)[..., -k][..., None]
    chosen = biased >= kth
    weights = jnp.where(chosen, scores, 0.0)
    if sizes["norm_topk_prob"]:
        weights = weights / (jnp.sum(weights, -1, keepdims=True) + 1e-20)
    return weights * sizes["routed_scaling_factor"]


def held_range(sizes, expert_offset=None, held=None):
    offset = sizes.get("expert_offset", 0) if expert_offset is None \
        else expert_offset
    return offset, sizes["n_routed_experts"] if held is None else held


def routed_experts(params, name, y, sizes, expert_offset=None, held=None):
    """The part of the routed result that the experts held here give: every
    held expert runs on every token, weighted by the router's table."""
    offset, held = held_range(sizes, expert_offset, held)
    weights = router(params, name, y, sizes)

    @jax.checkpoint
    def add_expert(out, expert):
        w, w_gate, w_up, w_down = expert
        return out + w[..., None] * mm(
            jax.nn.silu(mm(y, w_gate)) * mm(y, w_up), w_down), None
    # one held expert after another, as a loop would run them; a scan so
    # that the compiled program holds one expert's code and not ``held``
    # copies of it
    out, _ = jax.lax.scan(add_expert, jnp.zeros_like(y), (
        jnp.moveaxis(weights[..., offset:offset + held], -1, 0),
        params[name + "experts_gate_weight"][:held],
        params[name + "experts_up_weight"][:held],
        params[name + "experts_down_weight"][:held]))
    return out


def moe(params, name, y, sizes):
    out = routed_experts(params, name, y, sizes) if _ROUTED_EXPERTS \
        else jnp.zeros_like(y)
    if sizes["n_shared_experts"]:
        out = out + swiglu(params, name + "shared_", y)
    return out


def layer_parameters(params, i):
    """Layer ``i``'s parameters under their names without the layer's own
    prefix: the same keys for every layer of a kind."""
    prefix = f"model_layer{i}_"
    return {k[len(prefix):]: v for k, v in params.items()
            if k.startswith(prefix)}


def layer(own, h, sizes, attention):
    """One layer on its :func:`layer_parameters`, a dense one if it has a
    dense MLP's: ``(h, rows)``, rows the choices that landed on an expert
    held here (what its grouped products had to compute; 0 for a dense
    layer)."""
    eps = sizes["rms_norm_eps"]
    x = rms_norm(h, own["input_norm_weight"], eps)
    h = stored(h + mla(own, "attn_", x, sizes, attention))
    y = rms_norm(h, own["post_norm_weight"], eps)
    if "mlp_dense0_weight" in own:
        return stored(h + swiglu(own, "mlp_", y)), jnp.int32(0)
    offset, held = held_range(sizes)
    chosen = router(own, "moe_", y, sizes) > 0
    return stored(h + moe(own, "moe_", y, sizes)), \
        jnp.sum(chosen[..., offset:offset + held], dtype=jnp.int32)


def hidden_states(params, tokens, sizes, attention=blocked_attention):
    """``(h, rows)``: the last layer's output before the final norm, and
    :func:`layer`'s rows, one int32 a layer."""
    h = params["model_embed_weight"][tokens]
    rows = []
    for i in range(sizes["num_hidden_layers"]):
        h, routed = jax.checkpoint(functools.partial(
            layer, sizes=sizes, attention=attention))(
                layer_parameters(params, i), h)
        rows.append(routed)
    return h, jnp.stack(rows)


def next_token_nll(h, norm_weight, head_weight, wanted, sizes):
    """Sum over the positions of ``-log softmax(head(norm(h)))[wanted]``."""
    h = rms_norm(h, norm_weight, sizes["rms_norm_eps"])
    logp = jax.nn.log_softmax(mm(h, head_weight.T), axis=-1)
    return -jnp.sum(jnp.take_along_axis(
        logp, wanted.astype(jnp.int32)[..., None], axis=-1))


def logits(params, tokens, sizes, attention=blocked_attention):
    h, _ = hidden_states(params, tokens, sizes, attention)
    h = rms_norm(h, params["model_norm_weight"], sizes["rms_norm_eps"])
    return mm(h, params["lm_head_weight"].T)


def loss_and_rows(params, batch, sizes, attention=blocked_attention):
    """Mean next-token cross-entropy over every position of the batch
    (``targets`` are the ids shifted by one, made by the caller), float32
    with every matmul at full float32 precision; and :func:`layer`'s rows,
    summed over the batch."""
    tokens, targets = batch
    with jax.default_matmul_precision("highest"):
        params = {k: v.astype(jnp.float32) for k, v in params.items()}
        h, rows = hidden_states(params, tokens, sizes, attention)
        nll = next_token_nll(h, params["model_norm_weight"],
                             params["lm_head_weight"], targets, sizes)
        return nll / targets.size, rows


def loss(params, batch, sizes, attention=blocked_attention):
    return loss_and_rows(params, batch, sizes, attention)[0]


def gradient_program(sizes, watched, attention=blocked_attention,
                     stand_in=None):
    """``run(params, batch, gradients=True) -> (loss, rows, {name:
    gradient})`` for the layers' parameters named in ``watched``: what
    ``jax.grad`` of :func:`loss` gives for them (a tier-1 test holds the two
    together), computed one sequence and one layer at a time by chaining the
    layers' vjps from the loss down.  So the compiled programs are one
    layer's forward, one layer's backward and the head, each used again for
    every layer of its kind and every sequence, where ``jax.grad`` of the
    whole model at the timed sizes is one program of 0.4 GB of code that
    takes minutes to build, does not fit the compile cache, and holds more
    of the chip than the step under test leaves.  ``stand_in`` names a
    :func:`control` to trace under.  ``gradients=False`` stops after the
    loss and the rows."""
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
        _, vjp = jax.vjp(lambda own, h: layer(own, h, sizes, attention)[0],
                         own, h)
        g_own, g_h = vjp(ct)
        return g_h, {k: g_own[k] for k in wanted_kinds if k in g_own}
    forward = traced(lambda own, h: layer(own, h, sizes, attention))
    backward = traced(layer_vjp)
    nll_and_ct = traced(jax.value_and_grad(
        lambda h, norm, weight, wanted: next_token_nll(h, norm, weight,
                                                       wanted, sizes)))

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
