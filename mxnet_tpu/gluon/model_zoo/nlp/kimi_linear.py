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

**Latent attention** is ``deepseek_v3.MLAAttention`` with ``use_nope`` (the
configuration's ``mla_use_nope``): the 64 "rope" dims of q and the shared key
stay, nothing is rotated.  **The feed-forward** is a dense SwiGLU in the first
``first_k_dense_replace`` layers and ``decoder.MoEBlock`` in the others
(sigmoid scores over all ``num_experts``, the ``num_experts_per_token``
largest renormalised and scaled, one shared expert), holding ``experts_held``
of the experts from ``expert_offset`` on; ``vocab_size`` is the rows of the
embedding and the head held here.

Assumed where the published ``config.json`` has no key (the benchmark's
configuration file lists the same), each as the published architecture's
reference implementation (``flash-linear-attention``) has it: the low rank
being the head dim; SiLU, the L2 norm (eps 1e-6 under the root) and the q
scale; the decay's parametrisation and its draws (``exp(A_log)`` uniform on
[1, 16] a head; ``dt_bias`` the inverse softplus of ``dt`` log-uniform on
[1e-3, 1e-1]); the gate's sigmoid; the convolution without bias.
"""
from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp

from .... import random as _rnd
from ....base import MXNetError
from ....initializer import Initializer, Normal
from ...block import HybridBlock
from .decoder import DecoderLM, DecoderLayer, MoEBlock, dense, \
    expert_share, swiglu
from .deepseek_v3 import MLAAttention
from .llama import RMSNorm

__all__ = ["KimiLinearConfig", "KimiDeltaAttention", "kimi_linear_48b_a3b",
           "kimi_linear_tiny"]

_PUBLISHED_FULL = (4, 8, 12, 16, 20, 24, 27)


@dataclasses.dataclass
class KimiLinearConfig:
    """Sizes under the names of the published ``config.json``
    (``linear_attn_config`` flattened to ``kda_*``, ``short_conv_kernel_size``
    and the two layer lists)."""

    vocab_size: int = 163840
    hidden_size: int = 2304
    intermediate_size: int = 9216
    moe_intermediate_size: int = 1024
    num_hidden_layers: int = 27
    first_k_dense_replace: int = 1
    num_attention_heads: int = 32
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    mla_use_nope: bool = True
    kda_num_heads: int = 32
    kda_head_dim: int = 128
    short_conv_kernel_size: int = 4
    kda_layers: tuple | None = None
    full_attn_layers: tuple = _PUBLISHED_FULL
    num_experts: int = 256
    num_experts_per_token: int = 8
    num_shared_experts: int = 1
    routed_scaling_factor: float = 2.446
    moe_renormalize: bool = True
    rope_theta: float = 10000.0
    rms_norm_eps: float = 1e-5
    experts_held: int | None = None
    expert_offset: int = 0
    initializer_range: float = 0.02
    embedding_initializer_range: float | None = None

    def __post_init__(self):
        n = self.num_hidden_layers
        self.full_attn_layers = tuple(
            i for i in self.full_attn_layers if i <= n)
        self.kda_layers = tuple(
            i for i in range(1, n + 1) if i not in self.full_attn_layers) \
            if self.kda_layers is None else tuple(
                i for i in self.kda_layers if i <= n)
        self.experts_held = expert_share(
            self.num_experts, self.num_experts_per_token, self.experts_held,
            self.expert_offset)
        if sorted(self.kda_layers + self.full_attn_layers) != \
                list(range(1, n + 1)):
            raise MXNetError(
                f"kda_layers {self.kda_layers} and full_attn_layers "
                f"{self.full_attn_layers} do not give each of the layers 1.."
                f"{n} one mixer")


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
        std = cfg.initializer_range
        init = Normal(std)
        with self.name_scope():
            self.q_proj = dense(h * d, std, "q_proj")
            self.k_proj = dense(h * d, std, "k_proj")
            self.v_proj = dense(h * d, std, "v_proj")
            self.q_conv = self.params.get(
                "q_conv_weight", init=init,
                shape=(h * d, cfg.short_conv_kernel_size))
            self.k_conv = self.params.get(
                "k_conv_weight", init=init,
                shape=(h * d, cfg.short_conv_kernel_size))
            self.v_conv = self.params.get(
                "v_conv_weight", init=init,
                shape=(h * d, cfg.short_conv_kernel_size))
            self.f_a_proj = dense(d, std, "f_a_proj")
            self.f_b_proj = dense(h * d, std, "f_b_proj")
            self.b_proj = dense(h, std, "b_proj")
            self.g_a_proj = dense(d, std, "g_a_proj")
            self.g_b_proj = dense(h * d, std, "g_b_proj")
            self.a_log = self.params.get(
                "A_log", shape=(h,),
                init=_LogUniform(1.0, 16.0, jnp.log, False))
            self.dt_bias = self.params.get(
                "dt_bias", shape=(h * d,),
                init=_LogUniform(1e-3, 1e-1,
                                 lambda dt: dt + jnp.log(-jnp.expm1(-dt)),
                                 True))
            self.o_norm = RMSNorm(d, cfg.rms_norm_eps, prefix="o_norm_")
            self.o_proj = dense(cfg.hidden_size, std, "o_proj")

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


def _network(cfg):
    """The decoder with each layer's mixer from the two lists (counted from
    1), a dense SwiGLU in the first ``first_k_dense_replace`` layers and the
    experts in the others."""
    kda = functools.partial(KimiDeltaAttention, cfg, prefix="attn_")
    mla = functools.partial(MLAAttention, cfg, use_nope=cfg.mla_use_nope,
                            prefix="attn_")
    mlp = functools.partial(swiglu, cfg.hidden_size, cfg.intermediate_size,
                            "mlp_")
    moe = functools.partial(
        MoEBlock, hidden_size=cfg.hidden_size,
        expert_width=cfg.moe_intermediate_size, num_experts=cfg.num_experts,
        experts_held=cfg.experts_held, expert_offset=cfg.expert_offset,
        top_k=cfg.num_experts_per_token,
        shared_experts=cfg.num_shared_experts,
        routed_scaling_factor=cfg.routed_scaling_factor,
        norm_topk_prob=cfg.moe_renormalize, scoring_func="sigmoid",
        initializer_range=cfg.initializer_range, prefix="moe_")
    return DecoderLM(
        [functools.partial(DecoderLayer, cfg.hidden_size, cfg.rms_norm_eps,
                           kda if i + 1 in cfg.kda_layers else mla,
                           mlp if i < cfg.first_k_dense_replace else moe)
         for i in range(cfg.num_hidden_layers)],
        vocab_size=cfg.vocab_size, hidden_size=cfg.hidden_size,
        rms_norm_eps=cfg.rms_norm_eps,
        initializer_range=cfg.initializer_range,
        embedding_initializer_range=cfg.embedding_initializer_range)


def kimi_linear_48b_a3b(**overrides):
    """moonshotai/Kimi-Linear-48B-A3B-Instruct at its published sizes (49.1 B
    parameters: pass ``experts_held``, ``expert_offset``, ``vocab_size`` and
    ``num_hidden_layers`` for one chip's share)."""
    return _network(KimiLinearConfig(**overrides))


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
    return _network(KimiLinearConfig(**kw))
