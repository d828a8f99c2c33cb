"""Kimi Linear (``model_type: kimi_linear``, arXiv:2510.26692): a decoder with
two kinds of token mixer — Kimi Delta Attention, a gated delta rule with a
decay of its own for every key channel, in three layers of four, and latent
attention without positions in the fourth — over a dropless sigmoid-routed
expert layer with a shared expert, built for training through
``DataParallelTrainer``.  No positional encoding anywhere: the recurrence
carries order.

Per layer, with ``h`` the (B, T, hidden) residual stream, pre-norm: ``h +=
Mixer(RMSNorm(h)); h += FFN(RMSNorm(h))``.  The source counts its layers from
1; ``kda_layers`` and ``full_attn_layers`` say which mixer a layer takes.

**Kimi Delta Attention** (:class:`KimiDeltaAttention`), ``x = RMSNorm(h)``,
``H`` heads of ``d`` for keys and values alike:

- ``q = L2Norm(SiLU(Conv(x W_q)))``, ``k = L2Norm(SiLU(Conv(x W_k)))``, ``v =
  SiLU(Conv(x W_v))``: a causal depthwise convolution over time of kernel
  ``short_conv_kernel_size`` (``nd.causal_conv1d``), the L2 norm over each
  head's ``d``, q scaled by ``d^-1/2``;
- the decay, a vector a head and token: ``g = -exp(A_log) softplus(x W_f1
  W_f2 + dt_bias)`` (a low-rank pair, the rank ``d``; ``A_log`` a scalar a
  head, ``dt_bias`` one a channel), and the step size ``beta = sigmoid(x
  W_b)``, a scalar a head, both float32 (``nd.kda_gate``);
- the state ``S`` (d, d) a head from zero at the start of a sequence: ``S_t =
  (I - beta_t k_t k_t^T) Diag(exp(g_t)) S_{t-1} + beta_t k_t v_t^T``, ``o_t =
  S_t^T q_t`` (``nd.kda_attention``: a chunked scan,
  ``ops/linear_attention.py``);
- ``y = W_o(sigmoid(x W_g1 W_g2) * RMSNorm_head(o))``: an RMSNorm over each
  head's ``d`` with one learned weight of ``d``, a sigmoid gate through a
  second low-rank pair.

**Latent attention** is ``deepseek_v3.MLAAttention`` with ``mla_use_nope``:
the 64 "rope" dims of q and the shared key stay, nothing is rotated.  **The
feed-forward** is a dense SwiGLU in the first ``first_k_dense_replace`` layers
and ``deepseek_v3.MoEBlock`` in the others (sigmoid scores over all
``num_experts``, the ``num_experts_per_token`` largest renormalised and scaled,
one shared expert), holding ``experts_held`` of the experts from
``expert_offset`` on; ``vocab_size`` is the rows of the embedding and the head
held here.

Assumed where the published ``config.json`` has no key (the benchmark's
configuration file lists the same), each as the published architecture's
reference implementation (``flash-linear-attention``) has it: the low rank
being the head dim; SiLU, the L2 norm (eps 1e-6 under the root) and the q
scale; the decay's parametrisation and its draws (``exp(A_log)`` uniform on
[1, 16] a head; ``dt_bias`` the inverse softplus of ``dt`` log-uniform on
[1e-3, 1e-1]); the gate's sigmoid; the convolution without bias.
"""
from __future__ import annotations

import math
import types

import jax
import jax.numpy as jnp

from .... import random as _rnd
from ....base import MXNetError
from ....initializer import Initializer, Normal
from ... import nn
from ...block import HybridBlock
from .deepseek_v3 import MLAAttention, MoEBlock, _dense
from .llama import LlamaMLP, RMSNorm

__all__ = ["KimiLinearConfig", "KimiDeltaAttention", "KimiLinearLayer",
           "KimiLinearModel", "KimiLinearForCausalLM", "kimi_linear_48b_a3b",
           "kimi_linear_tiny"]

_PUBLISHED_FULL = (4, 8, 12, 16, 20, 24, 27)


class KimiLinearConfig:
    """Sizes under the names of the published ``config.json``
    (``linear_attn_config`` flattened to ``kda_*``, ``short_conv_kernel_size``
    and the two layer lists).  ``n_routed_experts``, ``n_shared_experts``,
    ``num_experts_per_tok``, ``norm_topk_prob`` and ``scoring_func`` are the
    same sizes under the names ``deepseek_v3.MoEBlock`` reads."""

    def __init__(self, vocab_size=163840, hidden_size=2304,
                 intermediate_size=9216, moe_intermediate_size=1024,
                 num_hidden_layers=27, first_k_dense_replace=1,
                 num_attention_heads=32, kv_lora_rank=512,
                 qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
                 mla_use_nope=True, kda_num_heads=32, kda_head_dim=128,
                 short_conv_kernel_size=4, kda_layers=None,
                 full_attn_layers=_PUBLISHED_FULL, num_experts=256,
                 num_experts_per_token=8, num_shared_experts=1,
                 routed_scaling_factor=2.446, moe_renormalize=True,
                 rope_theta=10000.0, rms_norm_eps=1e-5, experts_held=None,
                 expert_offset=0, initializer_range=0.02,
                 embedding_initializer_range=None):
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
        self.mla_use_nope = mla_use_nope
        self.kda_num_heads = kda_num_heads
        self.kda_head_dim = kda_head_dim
        self.short_conv_kernel_size = short_conv_kernel_size
        layers = range(1, num_hidden_layers + 1)
        self.full_attn_layers = tuple(
            i for i in full_attn_layers if i <= num_hidden_layers)
        self.kda_layers = tuple(
            i for i in layers if i not in self.full_attn_layers) \
            if kda_layers is None else tuple(
                i for i in kda_layers if i <= num_hidden_layers)
        self.n_routed_experts = self.num_experts = num_experts
        self.num_experts_per_tok = self.num_experts_per_token = \
            num_experts_per_token
        self.n_shared_experts = self.num_shared_experts = num_shared_experts
        self.routed_scaling_factor = routed_scaling_factor
        self.norm_topk_prob = self.moe_renormalize = moe_renormalize
        self.scoring_func = "sigmoid"
        self.rope_theta = rope_theta
        self.rms_norm_eps = rms_norm_eps
        self.experts_held = num_experts if experts_held is None \
            else experts_held
        self.expert_offset = expert_offset
        self.initializer_range = initializer_range
        self.embedding_initializer_range = initializer_range \
            if embedding_initializer_range is None \
            else embedding_initializer_range
        if sorted(self.kda_layers + self.full_attn_layers) != list(layers):
            raise MXNetError(
                f"kda_layers {self.kda_layers} and full_attn_layers "
                f"{self.full_attn_layers} do not give each of the layers 1.."
                f"{num_hidden_layers} one mixer")
        if num_experts_per_token > num_experts:
            raise MXNetError("num_experts_per_token exceeds num_experts")
        if not (0 <= expert_offset and self.experts_held >= 1 and
                expert_offset + self.experts_held <= num_experts):
            raise MXNetError(
                f"experts {expert_offset}..{expert_offset + self.experts_held}"
                f" are not among the {num_experts} experts")

    def mlp(self, width):
        """What ``LlamaMLP`` reads of a configuration, at this width."""
        return types.SimpleNamespace(hidden_size=self.hidden_size,
                                     intermediate_size=width,
                                     tensor_parallel=False)


class _LogUniform(Initializer):
    """``transform(u)`` for ``log u`` uniform on ``[log low, log high]``
    (``log_uniform``) or ``u`` itself uniform on ``[low, high]``, whatever
    the parameter is called (a name ending in ``bias`` too)."""

    def __init__(self, low, high, transform, log_uniform):
        super().__init__(low=low, high=high)
        self._draw = low, high, transform, log_uniform

    def _init_weight(self, name, arr):
        low, high, transform, log_uniform = self._draw
        if log_uniform:
            low, high = math.log(low), math.log(high)
        u = jax.random.uniform(_rnd.next_key(), arr.shape, jnp.float32,
                               low, high)
        arr._set_data(transform(jnp.exp(u) if log_uniform else u)
                      .astype(arr.data.dtype))

    _init_bias = _init_weight


class KimiDeltaAttention(HybridBlock):
    """Kimi Delta Attention (module docstring)."""

    def __init__(self, cfg, **kwargs):
        super().__init__(**kwargs)
        self.cfg = cfg
        h, d = cfg.kda_num_heads, cfg.kda_head_dim
        init = Normal(cfg.initializer_range)
        with self.name_scope():
            self.q_proj = _dense(h * d, cfg, "q_proj")
            self.k_proj = _dense(h * d, cfg, "k_proj")
            self.v_proj = _dense(h * d, cfg, "v_proj")
            self.q_conv = self.params.get(
                "q_conv_weight", init=init,
                shape=(h * d, cfg.short_conv_kernel_size))
            self.k_conv = self.params.get(
                "k_conv_weight", init=init,
                shape=(h * d, cfg.short_conv_kernel_size))
            self.v_conv = self.params.get(
                "v_conv_weight", init=init,
                shape=(h * d, cfg.short_conv_kernel_size))
            self.f_a_proj = _dense(d, cfg, "f_a_proj")
            self.f_b_proj = _dense(h * d, cfg, "f_b_proj")
            self.b_proj = _dense(h, cfg, "b_proj")
            self.g_a_proj = _dense(d, cfg, "g_a_proj")
            self.g_b_proj = _dense(h * d, cfg, "g_b_proj")
            self.a_log = self.params.get(
                "A_log", shape=(h,),
                init=_LogUniform(1.0, 16.0, jnp.log, False))
            self.dt_bias = self.params.get(
                "dt_bias", shape=(h * d,),
                init=_LogUniform(1e-3, 1e-1,
                                 lambda dt: dt + jnp.log(-jnp.expm1(-dt)),
                                 True))
            self.o_norm = RMSNorm(d, cfg.rms_norm_eps, prefix="o_norm_")
            self.o_proj = _dense(cfg.hidden_size, cfg, "o_proj")

    def hybrid_forward(self, F, x, q_conv, k_conv, v_conv, a_log, dt_bias):
        cfg = self.cfg
        with jax.named_scope("kda.project"):
            q, k, v = self.q_proj(x), self.k_proj(x), self.v_proj(x)
            decay = self.f_b_proj(self.f_a_proj(x))
            step = self.b_proj(x)
            gate = self.g_b_proj(self.g_a_proj(x))
        q = F.causal_conv1d(q, q_conv)
        k = F.causal_conv1d(k, k_conv)
        v = F.causal_conv1d(v, v_conv)
        g, beta = F.kda_gate(decay, step, a_log, dt_bias)
        o = F.kda_attention(q, k, v, g, beta, num_heads=cfg.kda_num_heads)
        with jax.named_scope("kda.out"):
            o = self.o_norm(F.reshape(o, (0, 0, cfg.kda_num_heads, -1)))
            o = F.reshape(o, (0, 0, -1)) * F.sigmoid(F.cast(gate, "float32"))
            return self.o_proj(o)


class KimiLinearLayer(HybridBlock):
    def __init__(self, cfg, kda, dense, **kwargs):
        super().__init__(**kwargs)
        with self.name_scope():
            self.input_norm = RMSNorm(cfg.hidden_size, cfg.rms_norm_eps,
                                      prefix="input_norm_")
            self.attention = (KimiDeltaAttention if kda else MLAAttention)(
                cfg, prefix="attn_")
            self.post_norm = RMSNorm(cfg.hidden_size, cfg.rms_norm_eps,
                                     prefix="post_norm_")
            self.mlp = LlamaMLP(cfg.mlp(cfg.intermediate_size),
                                prefix="mlp_") if dense \
                else MoEBlock(cfg, prefix="moe_")

    def hybrid_forward(self, F, x):
        x = x + self.attention(self.input_norm(x))
        return x + self.mlp(self.post_norm(x))


class KimiLinearModel(HybridBlock):
    def __init__(self, cfg, **kwargs):
        super().__init__(**kwargs)
        self.cfg = cfg
        with self.name_scope():
            self.embed = nn.Embedding(
                cfg.vocab_size, cfg.hidden_size, prefix="embed_",
                weight_initializer=Normal(cfg.embedding_initializer_range))
            self.layers = nn.HybridSequential(prefix="")
            for i in range(cfg.num_hidden_layers):
                self.layers.add(KimiLinearLayer(
                    cfg, kda=i + 1 in cfg.kda_layers,
                    dense=i < cfg.first_k_dense_replace,
                    prefix=f"layer{i}_"))
            self.norm = RMSNorm(cfg.hidden_size, cfg.rms_norm_eps,
                                prefix="norm_")

    def hybrid_forward(self, F, tokens):
        x = self.embed(tokens)
        for layer in self.layers:
            x = layer(x)
        return self.norm(x)

    def remat(self, active=True):
        """Per-layer ``jax.checkpoint``, as ``DeepseekV3Model.remat``."""
        for layer in self.layers:
            layer.hybridize(active, remat=active)


class KimiLinearForCausalLM(HybridBlock):
    """tokens (B, T) -> logits (B, T, vocab_size) over the rows of the
    vocabulary held here; the head is not tied to the embedding."""

    def __init__(self, cfg, **kwargs):
        super().__init__(**kwargs)
        self.cfg = cfg
        with self.name_scope():
            self.model = KimiLinearModel(cfg, prefix="model_")
            self.lm_head = _dense(cfg.vocab_size, cfg, "lm_head")

    def hybrid_forward(self, F, tokens):
        return self.lm_head(self.model(tokens))


def kimi_linear_48b_a3b(**overrides):
    """moonshotai/Kimi-Linear-48B-A3B-Instruct at its published sizes (49.1 B
    parameters: pass ``experts_held``, ``expert_offset``, ``vocab_size`` and
    ``num_hidden_layers`` for one chip's share)."""
    return KimiLinearForCausalLM(KimiLinearConfig(**overrides))


def kimi_linear_tiny(**overrides):
    """The tests' preset: every mechanism, toy widths — four layers, the
    third latent attention, the first dense."""
    kw = dict(vocab_size=128, hidden_size=64, intermediate_size=96,
              moe_intermediate_size=32, num_hidden_layers=4,
              num_attention_heads=4, kv_lora_rank=32, qk_nope_head_dim=16,
              qk_rope_head_dim=8, v_head_dim=16, kda_num_heads=4,
              kda_head_dim=16, full_attn_layers=(3,), num_experts=8,
              num_experts_per_token=2)
    kw.update(overrides)
    return KimiLinearForCausalLM(KimiLinearConfig(**kw))
