"""SDAR's mixture-of-experts decoder (``model_type: sdar_moe``), trained as
a block-diffusion language model: grouped-query attention and a dropless
softmax-routed expert layer with no shared expert in every layer, built for
training through ``DataParallelTrainer``.

Training sequence.  A batch row is ``x_t ⊕ x_0`` (B, 2T): the noisy copy
``x_t`` (each token of a block of ``block_length`` replaced by the mask
token with its block's probability ``t``) and the clean ``x_0`` behind it,
both halves at the positions 0 .. T - 1.  Every layer carries both halves.

Per layer, with ``h`` the (B, 2T, hidden) residual stream:

- ``x = RMSNorm(h)``; ``q = x W_q`` -> 32 heads of 128, ``k = x W_k``,
  ``v = x W_v`` -> 4 heads of 128, no bias; RMSNorm with a learned weight
  over each head of ``q`` and of ``k``; rotary in half-split pairs over all
  128 dims.
- attention (``nd.block_diffusion_attention``), head ``a`` reading kv head
  ``a // 8``, under the block-diffusion mask, ``blk(p) = p // block_length``
  within a half: a clean query sees the clean keys with ``blk(s) <=
  blk(t)``; a noisy query the clean keys with ``blk(s) < blk(t)`` and the
  noisy keys with ``blk(s) == blk(t)``; a clean query never sees a noisy
  key.  ``h += concat(o) W_o``.
- ``y = RMSNorm(h)``; ``g = softmax(y W_r)`` over all ``num_experts`` in
  float32; the ``num_experts_per_tok`` largest, divided by their sum;
  ``h += sum_{e chosen and held} g_e E_e(y)``, ``E_e`` SwiGLU of width
  ``moe_intermediate_size``: ``deepseek_v3.MoEBlock`` with a softmax router
  and no shared expert, holding ``experts_held`` experts from
  ``expert_offset`` on.

The network returns the logits of the noisy half only (B, T, vocab): the
head runs where the loss is taken.  :func:`block_diffusion_loss` is the
masked-denoising objective: position ``p`` of ``x_t`` predicts its own clean
token (no shift), weighted by ``1 / t`` of its block where it was masked
and 0 where it was not.

Assumed where the published ``config.json`` has no key (the benchmark's
configuration file lists the same): the per-head q / k norms (the lineage's
published block), the block length, the noise schedule and the loss
(BD3-LM, arXiv:2503.09573, which SDAR, arXiv:2510.06303, trains with).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ....base import MXNetError
from ....initializer import Normal
from ... import nn
from ...block import HybridBlock
from .deepseek_v3 import MoEBlock
from .llama import RMSNorm

__all__ = ["SDARMoeConfig", "BlockDiffusionAttention", "SDARMoeLayer",
           "SDARMoeModel", "SDARMoeForBlockDiffusion", "block_diffusion_loss",
           "sdar_moe_30b_a3b", "sdar_moe_tiny"]


class SDARMoeConfig:
    """Sizes under the names of the published ``config.json``, with
    ``block_length`` (the chat models' generation block) beside them.
    ``n_routed_experts``, ``n_shared_experts``, ``scoring_func`` and
    ``routed_scaling_factor`` are what ``deepseek_v3.MoEBlock`` reads.
    ``embedding_initializer_range`` is the embedding's own standard deviation
    (``initializer_range`` where None).  ``moe_fixed_rows`` is the expert
    layer's fixed amount of work (``parallel.moe.dropless_moe_apply``'s
    ``fixed_rows``)."""

    def __init__(self, vocab_size=151936, hidden_size=2048,
                 moe_intermediate_size=768, num_hidden_layers=48,
                 num_attention_heads=32, num_key_value_heads=4, head_dim=128,
                 num_experts=128, num_experts_per_tok=8, norm_topk_prob=True,
                 rope_theta=1000000.0, rms_norm_eps=1e-6, block_length=4,
                 experts_held=None, expert_offset=0, initializer_range=0.02,
                 embedding_initializer_range=None, moe_fixed_rows=None):
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.moe_intermediate_size = moe_intermediate_size
        self.num_hidden_layers = num_hidden_layers
        self.num_attention_heads = num_attention_heads
        self.num_key_value_heads = num_key_value_heads
        self.head_dim = head_dim
        self.n_routed_experts = self.num_experts = num_experts
        self.num_experts_per_tok = num_experts_per_tok
        self.norm_topk_prob = norm_topk_prob
        self.rope_theta = rope_theta
        self.rms_norm_eps = rms_norm_eps
        self.block_length = block_length
        self.experts_held = num_experts if experts_held is None \
            else experts_held
        self.expert_offset = expert_offset
        self.initializer_range = initializer_range
        self.embedding_initializer_range = initializer_range \
            if embedding_initializer_range is None \
            else embedding_initializer_range
        self.moe_fixed_rows = moe_fixed_rows
        self.n_shared_experts = 0
        self.scoring_func = "softmax"
        self.routed_scaling_factor = 1.0
        if num_attention_heads % num_key_value_heads:
            raise MXNetError("num_key_value_heads must divide "
                             "num_attention_heads")
        if head_dim % 2:
            raise MXNetError(f"head_dim {head_dim}: rotary takes pairs")
        if block_length < 1 or block_length & (block_length - 1):
            raise MXNetError(f"block_length {block_length} is not a power "
                             f"of two")
        if num_experts_per_tok > num_experts:
            raise MXNetError("num_experts_per_tok exceeds num_experts")
        if not (0 <= expert_offset and self.experts_held >= 1 and
                expert_offset + self.experts_held <= num_experts):
            raise MXNetError(
                f"experts {expert_offset}..{expert_offset + self.experts_held}"
                f" are not among the {num_experts} experts")


def _dense(units, cfg, name):
    return nn.Dense(units, use_bias=False, flatten=False, prefix=name + "_",
                    weight_initializer=Normal(cfg.initializer_range))


class BlockDiffusionAttention(HybridBlock):
    """Grouped-query attention under the block-diffusion mask (module
    docstring) over (B, 2T, hidden)."""

    def __init__(self, cfg, **kwargs):
        super().__init__(**kwargs)
        self.cfg = cfg
        h, hkv, d = cfg.num_attention_heads, cfg.num_key_value_heads, \
            cfg.head_dim
        with self.name_scope():
            self.q_proj = _dense(h * d, cfg, "q_proj")
            self.k_proj = _dense(hkv * d, cfg, "k_proj")
            self.v_proj = _dense(hkv * d, cfg, "v_proj")
            self.o_proj = _dense(cfg.hidden_size, cfg, "o_proj")
            self.q_norm = RMSNorm(d, cfg.rms_norm_eps, prefix="q_norm_")
            self.k_norm = RMSNorm(d, cfg.rms_norm_eps, prefix="k_norm_")

    def _head_norm(self, F, norm, a, heads):
        # (B, 2T, heads * d): the norm over each head's d
        return F.reshape(norm(F.reshape(a, (0, 0, heads, -1))), (0, 0, -1))

    def hybrid_forward(self, F, x):
        cfg = self.cfg
        with jax.named_scope("gqa.project"):
            q = self._head_norm(F, self.q_norm, self.q_proj(x),
                                cfg.num_attention_heads)
            k = self._head_norm(F, self.k_norm, self.k_proj(x),
                                cfg.num_key_value_heads)
            v = self.v_proj(x)
        out = F.block_diffusion_attention(
            q, k, v, num_heads=cfg.num_attention_heads,
            block_length=cfg.block_length, rope_theta=cfg.rope_theta)
        with jax.named_scope("gqa.project"):
            return self.o_proj(out)


class SDARMoeLayer(HybridBlock):
    def __init__(self, cfg, **kwargs):
        super().__init__(**kwargs)
        with self.name_scope():
            self.input_norm = RMSNorm(cfg.hidden_size, cfg.rms_norm_eps,
                                      prefix="input_norm_")
            self.attention = BlockDiffusionAttention(cfg, prefix="attn_")
            self.post_norm = RMSNorm(cfg.hidden_size, cfg.rms_norm_eps,
                                     prefix="post_norm_")
            self.moe = MoEBlock(cfg, prefix="moe_")

    def hybrid_forward(self, F, x):
        x = x + self.attention(self.input_norm(x))
        return x + self.moe(self.post_norm(x))


class SDARMoeModel(HybridBlock):
    """tokens ``x_t ⊕ x_0`` (B, 2T) -> the normalised hidden states of the
    noisy half (B, T, hidden)."""

    def __init__(self, cfg, **kwargs):
        super().__init__(**kwargs)
        self.cfg = cfg
        with self.name_scope():
            self.embed = nn.Embedding(
                cfg.vocab_size, cfg.hidden_size, prefix="embed_",
                weight_initializer=Normal(cfg.embedding_initializer_range))
            self.layers = nn.HybridSequential(prefix="")
            for i in range(cfg.num_hidden_layers):
                self.layers.add(SDARMoeLayer(cfg, prefix=f"layer{i}_"))
            self.norm = RMSNorm(cfg.hidden_size, cfg.rms_norm_eps,
                                prefix="norm_")

    def hybrid_forward(self, F, tokens):
        x = self.embed(tokens)
        for layer in self.layers:
            x = layer(x)
        return self.norm(F.slice_axis(x, axis=1, begin=0,
                                      end=tokens.shape[1] // 2))

    def remat(self, active=True):
        """Per-layer ``jax.checkpoint``, as ``DeepseekV3Model.remat``."""
        for layer in self.layers:
            layer.hybridize(active, remat=active)


class SDARMoeForBlockDiffusion(HybridBlock):
    """tokens ``x_t ⊕ x_0`` (B, 2T) -> the logits of the noisy half (B, T,
    vocab_size) over the rows of the vocabulary held here; the head is not
    tied to the embedding."""

    def __init__(self, cfg, **kwargs):
        super().__init__(**kwargs)
        self.cfg = cfg
        with self.name_scope():
            self.model = SDARMoeModel(cfg, prefix="model_")
            self.lm_head = _dense(cfg.vocab_size, cfg, "lm_head")

    def hybrid_forward(self, F, tokens):
        return self.lm_head(self.model(tokens))


def block_diffusion_loss():
    """``loss(logits, label)`` (B,): the masked-denoising loss of a batch
    row, ``(1 / T) sum_p w_p CE(logits_p, x0_p)``, the log-softmax taken in
    float32.  ``label`` (B, 2, T) float32: row 0 the clean ids ``x_0``, row
    1 the weights ``w_p`` — ``1 / t`` of ``p``'s block where ``p`` was
    masked, 0 where it was not.  The trainer's mean over the batch then
    gives ``(1 / (B T)) sum_{p masked} CE / t``."""
    from ....ndarray.ndarray import apply_nary

    def fn(logits, label):
        with jax.named_scope("bd.loss"):
            logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
            nll = -jnp.take_along_axis(
                logp, label[:, 0].astype(jnp.int32)[..., None], axis=-1)[..., 0]
            return jnp.mean(nll * label[:, 1].astype(jnp.float32), axis=-1)

    def loss(logits, label):
        return apply_nary(fn, [logits, label], name="block_diffusion_loss")
    return loss


def sdar_moe_30b_a3b(**overrides):
    """JetLM/SDAR-30B-A3B-Chat at its published sizes (pass
    ``experts_held``, ``vocab_size`` and ``num_hidden_layers`` for one
    chip's share)."""
    return SDARMoeForBlockDiffusion(SDARMoeConfig(**overrides))


def sdar_moe_tiny(**overrides):
    """The tests' preset: every mechanism at toy widths."""
    kw = dict(vocab_size=128, hidden_size=64, moe_intermediate_size=32,
              num_hidden_layers=2, num_attention_heads=4,
              num_key_value_heads=2, head_dim=16, num_experts=8,
              num_experts_per_tok=2)
    kw.update(overrides)
    return SDARMoeForBlockDiffusion(SDARMoeConfig(**kw))
