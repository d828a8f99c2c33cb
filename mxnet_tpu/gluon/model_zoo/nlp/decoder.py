"""The decoder that ``deepseek_v3``, ``keye_vl2``, ``kimi_linear`` and
``sdar_moe`` share, and the dropless expert layer they all use.

A network is ``model_`` then ``lm_head_``: ``model_embed_``, the layers
``model_layer{i}_`` and ``model_norm_``, then a head not tied to the
embedding.  A layer is pre-norm, ``h += mixer(RMSNorm(h)); h +=
ffn(RMSNorm(h))``, under ``input_norm_``, ``attn_``, ``post_norm_`` and
``mlp_`` (dense) or ``moe_`` (experts).  A model file gives the sizes and
each layer's constructor (its schedule); nothing here reads a
configuration.

:class:`MoEBlock` knows its share: ``num_experts`` is the router's width,
``experts_held`` and ``expert_offset`` say which of them live here (all by
default).  Choices of absent experts add nothing; that partial result is
what goes on (``parallel/moe.py``).  The experts' weights are stored
stacked, (held, in, out), the layout the grouped product takes.
"""
from __future__ import annotations

import types

import jax

from ....base import MXNetError
from ....initializer import Normal
from ... import nn
from ...block import HybridBlock
from .llama import LlamaMLP, RMSNorm

__all__ = ["MoEBlock", "DecoderLayer", "DecoderModel", "DecoderLM"]


def dense(units, initializer_range, name):
    """A projection without bias under ``name_``, N(0, range) weights."""
    return nn.Dense(units, use_bias=False, flatten=False, prefix=name + "_",
                    weight_initializer=Normal(initializer_range))


def swiglu(hidden_size, width, prefix):
    """A dense SwiGLU feed-forward of ``width`` (``LlamaMLP``)."""
    return LlamaMLP(types.SimpleNamespace(
        hidden_size=hidden_size, intermediate_size=width,
        tensor_parallel=False), prefix=prefix)


def head_norm(F, norm, a, heads):
    """``norm`` over each head's d of (B, T, heads * d)."""
    return F.reshape(norm(F.reshape(a, (0, 0, heads, -1))), (0, 0, -1))


def expert_share(num_experts, top_k, experts_held, expert_offset):
    """The experts held (all ``num_experts`` where None), once routing
    ``top_k`` of them and holding ``expert_offset ..`` are possible."""
    held = num_experts if experts_held is None else experts_held
    if top_k > num_experts:
        raise MXNetError(f"top-{top_k} routing exceeds the {num_experts} "
                         f"experts")
    if not (0 <= expert_offset and held >= 1 and
            expert_offset + held <= num_experts):
        raise MXNetError(f"experts {expert_offset}..{expert_offset + held}"
                         f" are not among the {num_experts} experts")
    return held


class MoEBlock(HybridBlock):
    """Router over all ``num_experts``, the ``experts_held`` routed experts
    from ``expert_offset`` on that live here, and ``shared_experts`` shared
    experts as one SwiGLU of that many times ``expert_width`` (whole on
    every chip).  ``scoring_func`` ``"sigmoid"``: sigmoid scores, the
    ``top_k`` largest of score plus a selection bias
    (``e_score_correction_bias``, a buffer without gradient), weights scaled
    by ``routed_scaling_factor``; ``"softmax"``: a softmax over all experts,
    no bias, no scale.  ``fixed_rows``: the layer's fixed amount of work
    (``parallel.moe.dropless_moe_apply``)."""

    def __init__(self, *, hidden_size, expert_width, num_experts, top_k,
                 norm_topk_prob, scoring_func, initializer_range,
                 experts_held=None, expert_offset=0, shared_experts=0,
                 routed_scaling_factor=1.0, fixed_rows=None, **kwargs):
        super().__init__(**kwargs)
        held = num_experts if experts_held is None else experts_held
        init = Normal(initializer_range)
        self._router = dict(top_k=top_k, norm_topk_prob=norm_topk_prob,
                            routed_scaling_factor=routed_scaling_factor,
                            scoring_func=scoring_func)
        self._experts = dict(expert_offset=expert_offset,
                             fixed_rows=fixed_rows)
        with self.name_scope():
            self.gate = self.params.get(
                "router_weight", shape=(num_experts, hidden_size), init=init)
            if scoring_func == "sigmoid":
                self.e_score_correction_bias = self.params.get(
                    "e_score_correction_bias", shape=(num_experts,),
                    init="zeros", grad_req="null")
            self.experts_gate = self.params.get(
                "experts_gate_weight", shape=(held, hidden_size, expert_width),
                init=init)
            self.experts_up = self.params.get(
                "experts_up_weight", shape=(held, hidden_size, expert_width),
                init=init)
            self.experts_down = self.params.get(
                "experts_down_weight",
                shape=(held, expert_width, hidden_size), init=init)
            self.shared_experts = swiglu(
                hidden_size, shared_experts * expert_width, "shared_") \
                if shared_experts else None

    def hybrid_forward(self, F, x, gate, experts_gate, experts_up,
                       experts_down, e_score_correction_bias=None):
        experts, weights = F.moe_router(x, gate, e_score_correction_bias,
                                        **self._router)
        out = F.moe_experts(x, experts, weights, experts_gate, experts_up,
                            experts_down, **self._experts)
        if self.shared_experts is None:
            return out
        with jax.named_scope("moe.shared"):
            return out + self.shared_experts(x)


class DecoderLayer(HybridBlock):
    """``h += mixer(RMSNorm(h)); h += ffn(RMSNorm(h))``.  ``mixer()`` and
    ``ffn()`` build the two blocks under this layer's prefix."""

    def __init__(self, hidden_size, rms_norm_eps, mixer, ffn, **kwargs):
        super().__init__(**kwargs)
        with self.name_scope():
            self.input_norm = RMSNorm(hidden_size, rms_norm_eps,
                                      prefix="input_norm_")
            self.attention = mixer()
            self.post_norm = RMSNorm(hidden_size, rms_norm_eps,
                                     prefix="post_norm_")
            self.mlp = ffn()

    def hybrid_forward(self, F, x):
        x = x + self.attention(self.input_norm(x))
        return x + self.mlp(self.post_norm(x))


class DecoderModel(HybridBlock):
    """The embedding, the layers ``layers[i](prefix=...)`` builds and the
    final norm, which :class:`DecoderLM` walks."""

    def __init__(self, layers, vocab_size, hidden_size, rms_norm_eps,
                 embedding_initializer_range, **kwargs):
        super().__init__(**kwargs)
        with self.name_scope():
            self.embed = nn.Embedding(
                vocab_size, hidden_size, prefix="embed_",
                weight_initializer=Normal(embedding_initializer_range))
            self.layers = nn.HybridSequential(prefix="")
            for i, layer in enumerate(layers):
                self.layers.add(layer(prefix=f"layer{i}_"))
            self.norm = RMSNorm(hidden_size, rms_norm_eps, prefix="norm_")

    def remat(self, active=True):
        """Per-layer ``jax.checkpoint``, as ``LlamaModel.remat``: only the
        layers' inputs stay in HBM, their interiors are computed again in
        the backward pass."""
        for layer in self.layers:
            layer.hybridize(active, remat=active)


class DecoderLM(HybridBlock):
    """tokens (B, T) -> logits (B, T, vocab_size) over the rows of the
    vocabulary held here.  The embedding's standard deviation is
    ``embedding_initializer_range``, ``initializer_range`` where None."""

    def __init__(self, layers, *, vocab_size, hidden_size, rms_norm_eps,
                 initializer_range, embedding_initializer_range=None,
                 **kwargs):
        super().__init__(**kwargs)
        if embedding_initializer_range is None:
            embedding_initializer_range = initializer_range
        with self.name_scope():
            self.model = DecoderModel(
                layers, vocab_size, hidden_size, rms_norm_eps,
                embedding_initializer_range, prefix="model_")
            self.lm_head = dense(vocab_size, initializer_range, "lm_head")

    def hybrid_forward(self, F, tokens):
        model = self.model
        x = model.embed(tokens)
        for layer in model.layers:
            x = layer(x)
        return self.lm_head(model.norm(x))
