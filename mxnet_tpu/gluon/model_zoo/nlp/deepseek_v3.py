"""DeepSeek-V3-style decoder LM (``model_type: deepseek_v3``): multi-head
latent attention and a dropless sigmoid-routed expert layer with shared
experts, built for training through ``DataParallelTrainer``.
:class:`MLAAttention` and :class:`MoEBlock` have two users: this decoder
(kanana) and ``kimi_linear``, whose fourth layers take the attention without
positions.

Per layer, with ``h`` the (B, T, hidden) residual stream:

- ``x = RMSNorm(h)``; **MLA**: ``q = x W_q`` -> heads of ``[q_nope | q_pe]``;
  ``[c | k_pe] = x W_kva`` (latent ``kv_lora_rank`` + one rotary key shared
  by all heads); ``c = RMSNorm(c)``; ``c W_kvb`` -> heads of
  ``[k_nope | v]``; RoPE on ``q_pe`` and ``k_pe`` unless the configuration
  says ``mla_use_nope`` (then the ``rope`` dims stay and nothing is rotated:
  ``nd.mla_attention(use_nope=True)``); causal
  ``softmax(q k^T (nope + rope)^-1/2) v`` -> ``W_o``.  The expanded form:
  no weight absorption, no cache (serving's business).  ``q_lora_rank`` is
  null in both configurations that use it, so W_q is one matrix.
- ``h += attn``; ``y = RMSNorm(h)``.  The first ``first_k_dense_replace``
  layers: ``h += SwiGLU(y)`` of width ``intermediate_size``.
- the others: ``s = sigmoid(y W_g)`` in float32; the ``num_experts_per_tok``
  largest of ``s + b`` are chosen (``b``: ``e_score_correction_bias``, a
  buffer that gets no gradient; groups are trivial: ``n_group`` 1); their
  weights are the unbiased ``s_i``, normalised and scaled by
  ``routed_scaling_factor``;
  ``h += sum_{i chosen and held} w_i E_i(y) + Shared(y)``, ``E_i`` SwiGLU of
  width ``moe_intermediate_size``, ``Shared`` one SwiGLU of
  ``n_shared_experts`` times that width.  No auxiliary loss.

**The expert layer knows its share.**  ``n_routed_experts`` is the router's
width; ``experts_held`` and ``expert_offset`` say which of them live here
(all by default).  Choices of absent experts add nothing; that partial result
is what goes on (``parallel/moe.py``).  ``vocab_size`` is the rows of the
embedding and the head held here: a sliced vocabulary is a smaller one.

Departures from the Hugging Face implementation, both without effect on a
result: HF de-interleaves the rotary dims and then rotates halves; here the
interleaved pairs are rotated in place (``ops.norm_rope.rope_interleaved``) — the
same fixed permutation of ``q_pe`` and ``k_pe`` leaves every ``q . k``
unchanged (``tests/test_deepseek_v3.py`` holds the two forms together).  The
experts' weights are stored stacked, (held, in, out), the layout the grouped
product takes.
"""
from __future__ import annotations

import types

import jax

from .... import telemetry as _telem
from ....base import MXNetError
from ....initializer import Normal
from ... import nn
from ...block import HybridBlock
from .llama import LlamaMLP, RMSNorm

__all__ = ["DeepseekV3Config", "MLAAttention", "MoEBlock", "DeepseekV3Layer",
           "DeepseekV3Model", "DeepseekV3ForCausalLM", "kanana_2_30b_a3b",
           "deepseek_v3_tiny"]


class DeepseekV3Config:
    """Sizes under the names of the published ``config.json``."""

    def __init__(self, vocab_size=128256, hidden_size=2048,
                 intermediate_size=6144, moe_intermediate_size=768,
                 num_hidden_layers=48, first_k_dense_replace=1,
                 num_attention_heads=32, kv_lora_rank=512,
                 qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
                 n_routed_experts=128, num_experts_per_tok=6,
                 n_shared_experts=2, routed_scaling_factor=2.448,
                 norm_topk_prob=True, rope_theta=1000000.0,
                 rms_norm_eps=1e-6, experts_held=None, expert_offset=0,
                 initializer_range=0.02):
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.intermediate_size = intermediate_size
        self.moe_intermediate_size = moe_intermediate_size
        self.num_hidden_layers = num_hidden_layers
        self.first_k_dense_replace = first_k_dense_replace
        self.num_attention_heads = num_attention_heads
        self.kv_lora_rank = kv_lora_rank
        self.qk_nope_head_dim = qk_nope_head_dim
        self.qk_rope_head_dim = qk_rope_head_dim
        self.v_head_dim = v_head_dim
        self.n_routed_experts = n_routed_experts
        self.num_experts_per_tok = num_experts_per_tok
        self.n_shared_experts = n_shared_experts
        self.routed_scaling_factor = routed_scaling_factor
        self.norm_topk_prob = norm_topk_prob
        self.rope_theta = rope_theta
        self.rms_norm_eps = rms_norm_eps
        self.experts_held = n_routed_experts if experts_held is None \
            else experts_held
        self.expert_offset = expert_offset
        self.initializer_range = initializer_range
        if qk_rope_head_dim % 2:
            raise MXNetError("qk_rope_head_dim must be even (rotary pairs)")
        if num_experts_per_tok > n_routed_experts:
            raise MXNetError("num_experts_per_tok exceeds n_routed_experts")
        if not (0 <= expert_offset and self.experts_held >= 1 and
                expert_offset + self.experts_held <= n_routed_experts):
            raise MXNetError(
                f"experts {expert_offset}..{expert_offset + self.experts_held}"
                f" are not among the {n_routed_experts} routed experts")

    def mlp(self, width):
        """What ``LlamaMLP`` reads of a configuration, at this width."""
        return types.SimpleNamespace(hidden_size=self.hidden_size,
                                     intermediate_size=width,
                                     tensor_parallel=False)


def _dense(units, cfg, name):
    return nn.Dense(units, use_bias=False, flatten=False, prefix=name + "_",
                    weight_initializer=Normal(cfg.initializer_range))


class MLAAttention(HybridBlock):
    """Multi-head latent attention, expanded form (module docstring)."""

    def __init__(self, cfg, **kwargs):
        super().__init__(**kwargs)
        self.cfg = cfg
        h = cfg.num_attention_heads
        with self.name_scope():
            self.q_proj = _dense(
                h * (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim), cfg,
                "q_proj")
            self.kv_a_proj = _dense(
                cfg.kv_lora_rank + cfg.qk_rope_head_dim, cfg, "kv_a_proj")
            self.kv_a_norm = RMSNorm(cfg.kv_lora_rank, cfg.rms_norm_eps,
                                     prefix="kv_a_norm_")
            self.kv_b_proj = _dense(
                h * (cfg.qk_nope_head_dim + cfg.v_head_dim), cfg,
                "kv_b_proj")
            self.o_proj = _dense(cfg.hidden_size, cfg, "o_proj")

    def hybrid_forward(self, F, x):
        cfg = self.cfg
        _telem.inc("mla.layers")
        with jax.named_scope("mla.project"):
            q = self.q_proj(x)
            kva = self.kv_a_proj(x)
            latent = F.slice_axis(kva, axis=-1, begin=0,
                                  end=cfg.kv_lora_rank)
            k_pe = F.slice_axis(kva, axis=-1, begin=cfg.kv_lora_rank,
                                end=None)
            kv = self.kv_b_proj(self.kv_a_norm(latent))
        out = F.mla_attention(
            q, kv, k_pe, num_heads=cfg.num_attention_heads,
            qk_nope_head_dim=cfg.qk_nope_head_dim,
            qk_rope_head_dim=cfg.qk_rope_head_dim,
            v_head_dim=cfg.v_head_dim, rope_theta=cfg.rope_theta,
            use_nope=getattr(cfg, "mla_use_nope", False))
        with jax.named_scope("mla.project"):
            return self.o_proj(out)


class MoEBlock(HybridBlock):
    """Router over all ``n_routed_experts``, the ``experts_held`` routed
    experts that live here, and the shared experts (whole on every chip).
    ``cfg.scoring_func`` (``"sigmoid"`` where a configuration has none) says
    which router: the sigmoid one with its selection bias, or a softmax over
    all experts with no bias and no scale (``keye_vl2``)."""

    def __init__(self, cfg, **kwargs):
        super().__init__(**kwargs)
        self.cfg = cfg
        d, w, held = cfg.hidden_size, cfg.moe_intermediate_size, \
            cfg.experts_held
        init = Normal(cfg.initializer_range)
        self.scoring_func = getattr(cfg, "scoring_func", "sigmoid")
        with self.name_scope():
            self.gate = self.params.get(
                "router_weight", shape=(cfg.n_routed_experts, d), init=init)
            if self.scoring_func == "sigmoid":
                self.e_score_correction_bias = self.params.get(
                    "e_score_correction_bias", shape=(cfg.n_routed_experts,),
                    init="zeros", grad_req="null")
            self.experts_gate = self.params.get(
                "experts_gate_weight", shape=(held, d, w), init=init)
            self.experts_up = self.params.get(
                "experts_up_weight", shape=(held, d, w), init=init)
            self.experts_down = self.params.get(
                "experts_down_weight", shape=(held, w, d), init=init)
            self.shared_experts = LlamaMLP(
                cfg.mlp(cfg.n_shared_experts * w), prefix="shared_") \
                if cfg.n_shared_experts else None

    def hybrid_forward(self, F, x, gate, experts_gate, experts_up,
                       experts_down, e_score_correction_bias=None):
        cfg = self.cfg
        experts, weights = F.moe_router(
            x, gate, e_score_correction_bias, top_k=cfg.num_experts_per_tok,
            routed_scaling_factor=cfg.routed_scaling_factor,
            norm_topk_prob=cfg.norm_topk_prob,
            scoring_func=self.scoring_func)
        out = F.moe_experts(x, experts, weights, experts_gate, experts_up,
                            experts_down, expert_offset=cfg.expert_offset,
                            fixed_rows=getattr(cfg, "moe_fixed_rows", None))
        if self.shared_experts is None:
            return out
        with jax.named_scope("moe.shared"):
            return out + self.shared_experts(x)


class DeepseekV3Layer(HybridBlock):
    def __init__(self, cfg, dense, **kwargs):
        super().__init__(**kwargs)
        with self.name_scope():
            self.input_norm = RMSNorm(cfg.hidden_size, cfg.rms_norm_eps,
                                      prefix="input_norm_")
            self.attention = MLAAttention(cfg, prefix="attn_")
            self.post_norm = RMSNorm(cfg.hidden_size, cfg.rms_norm_eps,
                                     prefix="post_norm_")
            self.mlp = LlamaMLP(cfg.mlp(cfg.intermediate_size),
                                prefix="mlp_") if dense \
                else MoEBlock(cfg, prefix="moe_")

    def hybrid_forward(self, F, x):
        x = x + self.attention(self.input_norm(x))
        return x + self.mlp(self.post_norm(x))


class DeepseekV3Model(HybridBlock):
    def __init__(self, cfg, **kwargs):
        super().__init__(**kwargs)
        self.cfg = cfg
        with self.name_scope():
            self.embed = nn.Embedding(
                cfg.vocab_size, cfg.hidden_size, prefix="embed_",
                weight_initializer=Normal(cfg.initializer_range))
            self.layers = nn.HybridSequential(prefix="")
            for i in range(cfg.num_hidden_layers):
                self.layers.add(DeepseekV3Layer(
                    cfg, dense=i < cfg.first_k_dense_replace,
                    prefix=f"layer{i}_"))
            self.norm = RMSNorm(cfg.hidden_size, cfg.rms_norm_eps,
                                prefix="norm_")

    def hybrid_forward(self, F, tokens):
        x = self.embed(tokens)
        for layer in self.layers:
            x = layer(x)
        return self.norm(x)

    def remat(self, active=True):
        """Per-layer ``jax.checkpoint``, as ``LlamaModel.remat``: only the
        layers' inputs stay in HBM, their interiors are computed again in
        the backward pass."""
        for layer in self.layers:
            layer.hybridize(active, remat=active)


class DeepseekV3ForCausalLM(HybridBlock):
    """tokens (B, T) -> logits (B, T, vocab_size) over the rows of the
    vocabulary held here; the head is not tied to the embedding."""

    def __init__(self, cfg, **kwargs):
        super().__init__(**kwargs)
        self.cfg = cfg
        with self.name_scope():
            self.model = DeepseekV3Model(cfg, prefix="model_")
            self.lm_head = _dense(cfg.vocab_size, cfg, "lm_head")

    def hybrid_forward(self, F, tokens):
        return self.lm_head(self.model(tokens))


def kanana_2_30b_a3b(**overrides):
    """kakaocorp/kanana-2-30b-a3b-instruct-2601 at its published sizes
    (30.7 B parameters: pass ``experts_held``, ``vocab_size`` and
    ``num_hidden_layers`` for one chip's share)."""
    return DeepseekV3ForCausalLM(DeepseekV3Config(**overrides))


def deepseek_v3_tiny(**overrides):
    """The tests' preset: every mechanism, toy widths."""
    kw = dict(vocab_size=128, hidden_size=64, intermediate_size=96,
              moe_intermediate_size=32, num_hidden_layers=3,
              first_k_dense_replace=1, num_attention_heads=4,
              kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
              v_head_dim=16, n_routed_experts=8, num_experts_per_tok=2,
              n_shared_experts=1, routed_scaling_factor=2.448)
    kw.update(overrides)
    return DeepseekV3ForCausalLM(DeepseekV3Config(**kw))
