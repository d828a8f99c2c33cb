"""DeepSeek-V3-style decoder LM (``model_type: deepseek_v3``): multi-head
latent attention and a dropless sigmoid-routed expert layer with shared
experts, built for training through ``DataParallelTrainer``.
:class:`MLAAttention` has two users: this decoder (kanana) and
``kimi_linear``, whose fourth layers take the attention without positions.

Per layer, with ``h`` the (B, T, hidden) residual stream:

- ``x = RMSNorm(h)``; **MLA**: ``q = x W_q`` -> heads of ``[q_nope | q_pe]``;
  ``[c | k_pe] = x W_kva`` (latent ``kv_lora_rank`` + one rotary key shared
  by all heads); ``c = RMSNorm(c)``; ``c W_kvb`` -> heads of
  ``[k_nope | v]``; RoPE on ``q_pe`` and ``k_pe`` unless the block is built
  with ``use_nope`` (then the ``rope`` dims stay and nothing is rotated:
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

**The expert layer knows its share** (``decoder.MoEBlock``):
``n_routed_experts`` is the router's width; ``experts_held`` and
``expert_offset`` say which of them live here.  ``vocab_size`` is the rows
of the embedding and the head held here: a sliced vocabulary is a smaller
one.

Departure from the Hugging Face implementation, without effect on a
result: HF de-interleaves the rotary dims and then rotates halves; here the
interleaved pairs are rotated in place (``ops.norm_rope.rope_interleaved``) — the
same fixed permutation of ``q_pe`` and ``k_pe`` leaves every ``q . k``
unchanged (``tests/test_deepseek_v3.py`` holds the two forms together).
"""
from __future__ import annotations

import dataclasses
import functools

import jax

from .... import telemetry as _telem
from ....base import MXNetError
from ...block import HybridBlock
from .decoder import DecoderLM, DecoderLayer, MoEBlock, dense, \
    expert_share, swiglu
from .llama import RMSNorm

__all__ = ["DeepseekV3Config", "MLAAttention", "kanana_2_30b_a3b",
           "deepseek_v3_tiny"]


@dataclasses.dataclass
class DeepseekV3Config:
    """Sizes under the names of the published ``config.json``."""

    vocab_size: int = 128256
    hidden_size: int = 2048
    intermediate_size: int = 6144
    moe_intermediate_size: int = 768
    num_hidden_layers: int = 48
    first_k_dense_replace: int = 1
    num_attention_heads: int = 32
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    n_routed_experts: int = 128
    num_experts_per_tok: int = 6
    n_shared_experts: int = 2
    routed_scaling_factor: float = 2.448
    norm_topk_prob: bool = True
    rope_theta: float = 1000000.0
    rms_norm_eps: float = 1e-6
    experts_held: int | None = None
    expert_offset: int = 0
    initializer_range: float = 0.02

    def __post_init__(self):
        self.experts_held = expert_share(
            self.n_routed_experts, self.num_experts_per_tok,
            self.experts_held, self.expert_offset)
        if self.qk_rope_head_dim % 2:
            raise MXNetError("qk_rope_head_dim must be even (rotary pairs)")


class MLAAttention(HybridBlock):
    """Multi-head latent attention, expanded form (module docstring), at the
    sizes ``cfg`` gives under DeepSeek-V3's names; ``use_nope``: no
    rotary."""

    def __init__(self, cfg, use_nope, **kwargs):
        super().__init__(**kwargs)
        self.cfg = cfg
        self.use_nope = use_nope
        h, std = cfg.num_attention_heads, cfg.initializer_range
        with self.name_scope():
            self.q_proj = dense(
                h * (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim), std,
                "q_proj")
            self.kv_a_proj = dense(
                cfg.kv_lora_rank + cfg.qk_rope_head_dim, std, "kv_a_proj")
            self.kv_a_norm = RMSNorm(cfg.kv_lora_rank, cfg.rms_norm_eps,
                                     prefix="kv_a_norm_")
            self.kv_b_proj = dense(
                h * (cfg.qk_nope_head_dim + cfg.v_head_dim), std,
                "kv_b_proj")
            self.o_proj = dense(cfg.hidden_size, std, "o_proj")

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
            use_nope=self.use_nope)
        with jax.named_scope("mla.project"):
            return self.o_proj(out)


def _network(cfg):
    """The decoder with latent attention in every layer, a dense SwiGLU in
    the first ``first_k_dense_replace`` layers and the experts in the
    others."""
    mla = functools.partial(MLAAttention, cfg, use_nope=False, prefix="attn_")
    mlp = functools.partial(swiglu, cfg.hidden_size, cfg.intermediate_size,
                            "mlp_")
    moe = functools.partial(
        MoEBlock, hidden_size=cfg.hidden_size,
        expert_width=cfg.moe_intermediate_size,
        num_experts=cfg.n_routed_experts, experts_held=cfg.experts_held,
        expert_offset=cfg.expert_offset, top_k=cfg.num_experts_per_tok,
        shared_experts=cfg.n_shared_experts,
        routed_scaling_factor=cfg.routed_scaling_factor,
        norm_topk_prob=cfg.norm_topk_prob, scoring_func="sigmoid",
        initializer_range=cfg.initializer_range, prefix="moe_")
    return DecoderLM(
        [functools.partial(DecoderLayer, cfg.hidden_size, cfg.rms_norm_eps,
                           mla, mlp if i < cfg.first_k_dense_replace else moe)
         for i in range(cfg.num_hidden_layers)],
        vocab_size=cfg.vocab_size, hidden_size=cfg.hidden_size,
        rms_norm_eps=cfg.rms_norm_eps,
        initializer_range=cfg.initializer_range)


def kanana_2_30b_a3b(**overrides):
    """kakaocorp/kanana-2-30b-a3b-instruct-2601 at its published sizes
    (30.7 B parameters: pass ``experts_held``, ``vocab_size`` and
    ``num_hidden_layers`` for one chip's share)."""
    return _network(DeepseekV3Config(**overrides))


def deepseek_v3_tiny(**overrides):
    """The tests' preset: every mechanism, toy widths."""
    kw = dict(vocab_size=128, hidden_size=64, intermediate_size=96,
              moe_intermediate_size=32, num_hidden_layers=3,
              first_k_dense_replace=1, num_attention_heads=4,
              kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
              v_head_dim=16, n_routed_experts=8, num_experts_per_tok=2,
              n_shared_experts=1, routed_scaling_factor=2.448)
    kw.update(overrides)
    return _network(DeepseekV3Config(**kw))
