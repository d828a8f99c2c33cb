"""The language model of Keye-VL-2.0 (``model_type: KeyeVL2``): grouped-query
attention under a learned sparse-attention indexer, and a dropless
softmax-routed expert layer with no shared expert in every layer, built for
training through ``DataParallelTrainer``.  The vision tower is not part of
it: its positions arrive as the three streams of the rotary embedding.

Per layer, with ``h`` the (B, T, hidden) residual stream and ``p`` the (3, B,
T) positions (temporal, height, width; for text all three the token's index):

- ``x = RMSNorm(h)``; ``q = x W_q`` -> 32 heads of 128, ``k = x W_k``,
  ``v = x W_v`` -> 4 heads of 128, no bias; RMSNorm with a learned weight
  over each head of ``q`` and of ``k``; rotary in half-split pairs (``i``
  with ``i + 64``), the 64 frequencies divided among the position streams
  by ``mrope_section`` (16 / 24 / 24).
- the **indexer**, on ``stop_gradient(x)``: ``qI`` -> 16 heads of 64, ``kI``
  -> one head of 64, rotary over all 64 dims at the temporal position,
  ``w`` -> 16 weights a token; ``I[t, s] = 16^-1/2 64^-1/2 sum_j w[t, j]
  relu(qI[t, j] . kI[s])``; query ``t`` attends to the keys of its causal
  past whose score is at least the ``topk``-th largest (``sa_config.topk``
  2048; all of them while ``t < topk``).
- head ``a`` reads key-value head ``a // 8`` over that selection; ``h +=
  concat(o) W_o``.  ``nd.sparse_gq_attention`` (``ops/sparse_attention.py``)
  is the indexer's scores, the selection, the attention and the indexer's
  alignment loss ``L_I = mean_t KL(mean_a P[t, a, .] || softmax_{S_t}
  I[t, .])``, the one thing the indexer's three matrices get a gradient
  from (the attention's parameters get none from it).
- ``y = RMSNorm(h)``; ``g = softmax(y W_r)`` over all ``num_experts`` in
  float32; the ``num_experts_per_tok`` largest, divided by their sum
  (``norm_topk_prob``); ``h += sum_{e chosen and held} g_e E_e(y)``, ``E_e``
  SwiGLU of width ``moe_intermediate_size``: ``decoder.MoEBlock`` with a
  softmax router and no shared expert, holding ``experts_held`` of the
  experts from ``expert_offset`` on.

The network returns ``(logits, index_loss)``: ``index_loss`` (B,) is the sum
of the layers' ``L_I``, which the training loss adds to the cross-entropy
(:func:`causal_lm_loss`), as ``parallel.moe.MoEDense`` hands on its balance
loss.

Assumed where the published ``config.json`` has no key (the benchmark's
configuration file lists the same): the per-head q / k norms (the lineage's
published block), the indexer's rotary and scale (DeepSeek Sparse Attention
as published), ``L_I`` (its sparse-training stage), ``q_chunk_size`` /
``kv_chunk_size`` as tiles that change no result.
"""
from __future__ import annotations

import dataclasses
import functools

import jax

from ....base import MXNetError
from ....initializer import Normal
from ...block import HybridBlock
from .decoder import DecoderLM, MoEBlock, dense, expert_share, head_norm
from .llama import RMSNorm

__all__ = ["KeyeVL2Config", "SparseIndexer", "SparseGQAttention",
           "KeyeVL2Layer", "KeyeVL2LM", "causal_lm_loss", "keye_vl2_30b_a3b",
           "keye_vl2_tiny"]


@dataclasses.dataclass
class KeyeVL2Config:
    """Sizes under the names of the published ``config.json`` (``sa_config``
    flattened to ``indexer_*`` and ``topk``).  ``embedding_initializer_range``
    is the embedding's own standard deviation (``initializer_range`` where
    None)."""

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
    rope_theta: float = 10000000.0
    mrope_section: tuple = (16, 24, 24)
    rms_norm_eps: float = 1e-6
    indexer_num_heads: int = 16
    indexer_head_dim: int = 64
    topk: int = 2048
    experts_held: int | None = None
    expert_offset: int = 0
    initializer_range: float = 0.02
    embedding_initializer_range: float | None = None

    def __post_init__(self):
        self.mrope_section = tuple(self.mrope_section)
        self.experts_held = expert_share(
            self.num_experts, self.num_experts_per_tok, self.experts_held,
            self.expert_offset)
        if self.num_attention_heads % self.num_key_value_heads:
            raise MXNetError("num_key_value_heads must divide "
                             "num_attention_heads")
        d = self.head_dim
        if d % 2 or self.indexer_head_dim % 2 or \
                sum(self.mrope_section) != d // 2:
            raise MXNetError(
                f"mrope_section {self.mrope_section} does not divide the "
                f"{d // 2} rotary frequencies of head_dim {d}")


class SparseIndexer(HybridBlock):
    """The indexer's three matrices on the layer's normalised input, which
    it reads without a gradient: ``(qI, kI, x_hat, W_w)``, the index weights
    left to the attention op (float32 there)."""

    def __init__(self, cfg, **kwargs):
        super().__init__(**kwargs)
        with self.name_scope():
            self.wq_proj = dense(cfg.indexer_num_heads * cfg.indexer_head_dim,
                                 cfg.initializer_range, "wq_proj")
            self.wk_proj = dense(cfg.indexer_head_dim, cfg.initializer_range,
                                 "wk_proj")
            self.weights_proj = self.params.get(
                "weights_proj_weight",
                shape=(cfg.indexer_num_heads, cfg.hidden_size),
                init=Normal(cfg.initializer_range))

    def hybrid_forward(self, F, x, weights_proj):
        x = F.stop_gradient(x)
        return self.wq_proj(x), self.wk_proj(x), x, weights_proj


class SparseGQAttention(HybridBlock):
    """Grouped-query attention over the indexer's selection (module
    docstring): ``(out, index_loss)``."""

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

    def hybrid_forward(self, F, x, q_index, k_index, x_index, w_index,
                       positions=None):
        cfg = self.cfg
        with jax.named_scope("gqa.project"):
            q = head_norm(F, self.q_norm, self.q_proj(x),
                          cfg.num_attention_heads)
            k = head_norm(F, self.k_norm, self.k_proj(x),
                          cfg.num_key_value_heads)
            v = self.v_proj(x)
        out, index_loss = F.sparse_gq_attention(
            q, k, v, q_index, k_index, x_index, w_index, positions,
            num_heads=cfg.num_attention_heads, topk=cfg.topk,
            rope_theta=cfg.rope_theta, mrope_section=cfg.mrope_section)
        with jax.named_scope("gqa.project"):
            return self.o_proj(out), index_loss


class KeyeVL2Layer(HybridBlock):
    """``(h, index_loss_so_far[, positions]) -> (h, index_loss_so_far)``:
    the pre-norm pair of ``decoder.DecoderLayer`` with the indexer beside
    the attention."""

    def __init__(self, cfg, moe, **kwargs):
        super().__init__(**kwargs)
        with self.name_scope():
            self.input_norm = RMSNorm(cfg.hidden_size, cfg.rms_norm_eps,
                                      prefix="input_norm_")
            self.indexer = SparseIndexer(cfg, prefix="indexer_")
            self.attention = SparseGQAttention(cfg, prefix="attn_")
            self.post_norm = RMSNorm(cfg.hidden_size, cfg.rms_norm_eps,
                                     prefix="post_norm_")
            self.mlp = moe()

    def hybrid_forward(self, F, x, index_loss, positions=None):
        normed = self.input_norm(x)
        attn, layer_loss = self.attention(normed, *self.indexer(normed),
                                          positions)
        x = x + attn
        return x + self.mlp(self.post_norm(x)), index_loss + layer_loss


class KeyeVL2LM(DecoderLM):
    """tokens (B, T) [, positions (3, B, T)] -> ``(logits (B, T,
    vocab_size), index_loss (B,))``: the decoder, its layers carrying the
    indexer's alignment loss."""

    def hybrid_forward(self, F, tokens, positions=None):
        model = self.model
        x = model.embed(tokens)
        index_loss = F.zeros((tokens.shape[0],), dtype="float32")
        for layer in model.layers:
            x, index_loss = layer(x, index_loss, positions)
        return self.lm_head(model.norm(x)), index_loss


def causal_lm_loss():
    """``loss((logits, index_loss), targets)`` (B,): the mean next-token
    cross-entropy over every position, the log-softmax taken in float32,
    plus the indexer's alignment loss."""
    from ... import loss as gloss
    ce = gloss.SoftmaxCrossEntropyLoss()

    def loss(outputs, targets):
        logits, index_loss = outputs
        return ce(logits.astype("float32"), targets) + index_loss
    return loss


def _network(cfg):
    moe = functools.partial(
        MoEBlock, hidden_size=cfg.hidden_size,
        expert_width=cfg.moe_intermediate_size, num_experts=cfg.num_experts,
        experts_held=cfg.experts_held, expert_offset=cfg.expert_offset,
        top_k=cfg.num_experts_per_tok, norm_topk_prob=cfg.norm_topk_prob,
        scoring_func="softmax", initializer_range=cfg.initializer_range,
        prefix="moe_")
    return KeyeVL2LM(
        [functools.partial(KeyeVL2Layer, cfg, moe)
         for _ in range(cfg.num_hidden_layers)],
        vocab_size=cfg.vocab_size, hidden_size=cfg.hidden_size,
        rms_norm_eps=cfg.rms_norm_eps,
        initializer_range=cfg.initializer_range,
        embedding_initializer_range=cfg.embedding_initializer_range)


def keye_vl2_30b_a3b(**overrides):
    """Kwai-Keye/Keye-VL-2.0-30B-A3B's language model at its published sizes
    (pass ``experts_held``, ``vocab_size`` and ``num_hidden_layers`` for one
    chip's share)."""
    return _network(KeyeVL2Config(**overrides))


def keye_vl2_tiny(**overrides):
    """The tests' preset: every mechanism, toy widths (a selection of 8 keys
    among up to 32)."""
    kw = dict(vocab_size=128, hidden_size=64, moe_intermediate_size=32,
              num_hidden_layers=2, num_attention_heads=4,
              num_key_value_heads=2, head_dim=16, num_experts=8,
              num_experts_per_tok=2, mrope_section=(2, 3, 3),
              indexer_num_heads=2, indexer_head_dim=8, topk=8)
    kw.update(overrides)
    return _network(KeyeVL2Config(**kw))
