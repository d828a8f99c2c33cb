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
  ``moe_intermediate_size``: ``decoder.MoEBlock`` with a softmax router
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

import dataclasses
import functools

import jax
import jax.numpy as jnp

from ....base import MXNetError
from ...block import HybridBlock
from .decoder import DecoderLM, DecoderLayer, MoEBlock, dense, \
    expert_share, head_norm
from .llama import RMSNorm

__all__ = ["SDARMoeConfig", "BlockDiffusionAttention",
           "SDARMoeForBlockDiffusion", "block_diffusion_loss",
           "sdar_moe_30b_a3b", "sdar_moe_tiny"]


@dataclasses.dataclass
class SDARMoeConfig:
    """Sizes under the names of the published ``config.json``, with
    ``block_length`` (the chat models' generation block) beside them.
    ``embedding_initializer_range`` is the embedding's own standard deviation
    (``initializer_range`` where None).  ``moe_fixed_rows`` is the expert
    layer's fixed amount of work (``parallel.moe.dropless_moe_apply``'s
    ``fixed_rows``)."""

    vocab_size: int = 151936
    hidden_size: int = 2048
    moe_intermediate_size: int = 768
    num_hidden_layers: int = 48
    num_attention_heads: int = 32
    num_key_value_heads: int = 4
    head_dim: int = 128
    num_experts: int = 128
    num_experts_per_tok: int = 8
    norm_topk_prob: bool = True
    rope_theta: float = 1000000.0
    rms_norm_eps: float = 1e-6
    block_length: int = 4
    experts_held: int | None = None
    expert_offset: int = 0
    initializer_range: float = 0.02
    embedding_initializer_range: float | None = None
    moe_fixed_rows: int | None = None

    def __post_init__(self):
        self.experts_held = expert_share(
            self.num_experts, self.num_experts_per_tok, self.experts_held,
            self.expert_offset)
        if self.num_attention_heads % self.num_key_value_heads:
            raise MXNetError("num_key_value_heads must divide "
                             "num_attention_heads")
        if self.head_dim % 2:
            raise MXNetError(f"head_dim {self.head_dim}: rotary takes pairs")
        b = self.block_length
        if b < 1 or b & (b - 1):
            raise MXNetError(f"block_length {b} is not a power of two")


class BlockDiffusionAttention(HybridBlock):
    """Grouped-query attention under the block-diffusion mask (module
    docstring) over (B, 2T, hidden)."""

    def __init__(self, cfg, **kwargs):
        super().__init__(**kwargs)
        self.cfg = cfg
        h, hkv, d = cfg.num_attention_heads, cfg.num_key_value_heads, \
            cfg.head_dim
        std = cfg.initializer_range
        with self.name_scope():
            self.q_proj = dense(h * d, std, "q_proj")
            self.k_proj = dense(hkv * d, std, "k_proj")
            self.v_proj = dense(hkv * d, std, "v_proj")
            self.o_proj = dense(cfg.hidden_size, std, "o_proj")
            self.q_norm = RMSNorm(d, cfg.rms_norm_eps, prefix="q_norm_")
            self.k_norm = RMSNorm(d, cfg.rms_norm_eps, prefix="k_norm_")

    def hybrid_forward(self, F, x):
        cfg = self.cfg
        with jax.named_scope("gqa.project"):
            q = head_norm(F, self.q_norm, self.q_proj(x),
                          cfg.num_attention_heads)
            k = head_norm(F, self.k_norm, self.k_proj(x),
                          cfg.num_key_value_heads)
            v = self.v_proj(x)
        out = F.block_diffusion_attention(
            q, k, v, num_heads=cfg.num_attention_heads,
            block_length=cfg.block_length, rope_theta=cfg.rope_theta)
        with jax.named_scope("gqa.project"):
            return self.o_proj(out)


class SDARMoeForBlockDiffusion(DecoderLM):
    """tokens ``x_t ⊕ x_0`` (B, 2T) -> the logits of the noisy half (B, T,
    vocab_size): the decoder, read out on the noisy half only."""

    def hybrid_forward(self, F, tokens):
        model = self.model
        x = model.embed(tokens)
        for layer in model.layers:
            x = layer(x)
        return self.lm_head(model.norm(
            F.slice_axis(x, axis=1, begin=0, end=tokens.shape[1] // 2)))


def _network(cfg):
    mixer = functools.partial(BlockDiffusionAttention, cfg, prefix="attn_")
    moe = functools.partial(
        MoEBlock, hidden_size=cfg.hidden_size,
        expert_width=cfg.moe_intermediate_size, num_experts=cfg.num_experts,
        experts_held=cfg.experts_held, expert_offset=cfg.expert_offset,
        top_k=cfg.num_experts_per_tok, norm_topk_prob=cfg.norm_topk_prob,
        scoring_func="softmax", fixed_rows=cfg.moe_fixed_rows,
        initializer_range=cfg.initializer_range, prefix="moe_")
    return SDARMoeForBlockDiffusion(
        [functools.partial(DecoderLayer, cfg.hidden_size, cfg.rms_norm_eps,
                           mixer, moe)
         for _ in range(cfg.num_hidden_layers)],
        vocab_size=cfg.vocab_size, hidden_size=cfg.hidden_size,
        rms_norm_eps=cfg.rms_norm_eps,
        initializer_range=cfg.initializer_range,
        embedding_initializer_range=cfg.embedding_initializer_range)


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
    return _network(SDARMoeConfig(**overrides))


def sdar_moe_tiny(**overrides):
    """The tests' preset: every mechanism at toy widths."""
    kw = dict(vocab_size=128, hidden_size=64, moe_intermediate_size=32,
              num_hidden_layers=2, num_attention_heads=4,
              num_key_value_heads=2, head_dim=16, num_experts=8,
              num_experts_per_tok=2)
    kw.update(overrides)
    return _network(SDARMoeConfig(**kw))
