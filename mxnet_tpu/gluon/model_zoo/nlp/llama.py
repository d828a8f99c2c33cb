"""Llama-family decoder LM (the BASELINE.json Llama-3-8B stretch config).

No reference counterpart exists (the fork predates Llama; SURVEY.md §2.5
lists TP/SP as new capabilities) — this is the TPU-native flagship decoder:
RMSNorm + RoPE + grouped-query attention + SwiGLU, attention through the
Pallas flash kernel (ops/flash_attention.py), with two scaling hooks:

- tensor parallel: `tensor_parallel=True` swaps QKV/MLP projections for
  ParallelDense (megatron column/row split over the mesh 'tp' axis; XLA
  inserts the all-reduces from the sharding algebra).
- context parallel: `context_parallel=True` routes attention through
  parallel.ring_attention over the mesh 'sp' axis (neighbour ppermute of
  K/V blocks riding the ICI ring) for sequences longer than one chip's HBM;
  `context_parallel="ulysses"` selects the all-to-all head-scatter scheme
  instead (parallel.ulysses — 4 all-to-alls/layer, heads must divide the
  'sp' size; GQA kv repeated after the wire hop).
"""
from __future__ import annotations

import math

from ....base import MXNetError
from ...block import HybridBlock
from ... import nn
# RMSNorm and RoPE math: one source for the registered ops, the blocks here
# and the serving engine's decode steps
from ....ops.norm_rope import rms_norm as _rms, \
    rope_interleaved as _rot_interleaved

__all__ = ["LlamaConfig", "LlamaModel", "LlamaForCausalLM", "RMSNorm",
           "llama3_8b", "llama_tiny"]


class LlamaConfig:
    def __init__(self, vocab_size=128256, hidden_size=4096,
                 intermediate_size=14336, num_layers=32, num_heads=32,
                 num_kv_heads=8, max_seq_len=8192, rope_theta=500000.0,
                 rms_eps=1e-5, tie_embeddings=False,
                 tensor_parallel=False, context_parallel=False):
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.intermediate_size = intermediate_size
        self.num_layers = num_layers
        self.num_heads = num_heads
        self.num_kv_heads = num_kv_heads
        self.max_seq_len = max_seq_len
        self.rope_theta = rope_theta
        self.rms_eps = rms_eps
        self.tie_embeddings = tie_embeddings
        self.tensor_parallel = tensor_parallel
        self.context_parallel = context_parallel
        if hidden_size % num_heads:
            raise MXNetError("num_heads must divide hidden_size")
        if num_heads % num_kv_heads:
            raise MXNetError("num_kv_heads must divide num_heads")
        self.head_dim = hidden_size // num_heads


# Query rows fed to the cache-attention einsums are padded to this many
# rows: XLA CPU lowers an M=1 batched dot to a gemv whose accumulation
# order differs from the gemm the full forward runs, while every M>=2
# gemm is bitwise row-stable (verified empirically; tests/test_serving.py
# decode-parity gate).  Padding one duplicate row buys bitwise equality
# between single-token decode and the full-forward attention.
_QPAD = 2


def _cache_attention(q, ck, cv, valid, scale):
    """Single-token attention against a KV cache, shared by
    ``LlamaForCausalLM.generate`` and the serving engine
    (``mxnet_tpu.serving``) — one source so decode parity can't drift.

    Mirrors ``ops.flash_attention._scan_forward``'s single-block
    online-softmax op-for-op (same einsum specs, same mask constant,
    same normalization order) so that decode-with-cache logits are
    BITWISE equal to the full forward's last-row logits in fp32.

    q: (B, H, D) current-position queries (already rotated);
    ck/cv: (B, KVH, L, D) cache (unrepeated GQA heads);
    valid: (B, L) bool, True where the cache position participates;
    scale: softmax scale (1/sqrt(D) — multiplied, like the flash path).
    Returns (B, H*D).
    """
    import jax.numpy as jnp
    from ....ops.flash_attention import _NEG_INF
    b, h, d = q.shape
    kvh, L = ck.shape[1], ck.shape[2]
    rep = h // kvh
    kr = jnp.repeat(ck, rep, axis=1).reshape(b * h, L, d)
    vr = jnp.repeat(cv, rep, axis=1).reshape(b * h, L, d)
    q2 = jnp.broadcast_to(q.reshape(b * h, 1, d), (b * h, _QPAD, d))
    s = jnp.einsum("bqd,bkd->bqk", q2, kr,
                   preferred_element_type=jnp.float32) * scale
    vmask = jnp.repeat(valid[:, None, :], h, axis=1).reshape(b * h, 1, L)
    s = jnp.where(vmask, s, _NEG_INF)
    # single-block flash recurrence with the initial carry folded in,
    # matching _scan_forward's first (only) step exactly
    m0 = jnp.full((b * h, _QPAD, 1), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((b * h, _QPAD, 1), jnp.float32)
    acc0 = jnp.zeros((b * h, _QPAD, d), jnp.float32)
    m = jnp.maximum(m0, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m)
    alpha = jnp.exp(m0 - m)
    l = l0 * alpha + jnp.sum(p, axis=-1, keepdims=True)
    acc = acc0 * alpha + jnp.einsum("bqk,bkd->bqd", p.astype(cv.dtype), vr,
                                    preferred_element_type=jnp.float32)
    out = (acc / jnp.maximum(l, 1e-30)).astype(q.dtype)
    return out[:, 0].reshape(b, h * d)


class RMSNorm(HybridBlock):
    """Root-mean-square norm (no mean subtraction, no bias)."""

    def __init__(self, hidden_size, eps=1e-5, **kwargs):
        super().__init__(**kwargs)
        self._eps = eps
        with self.name_scope():
            self.weight = self.params.get("weight", shape=(hidden_size,),
                                          init="ones")

    def hybrid_forward(self, F, x, weight):
        # the registered op, so that amp places it (float32)
        return F.rms_norm(x, weight, eps=self._eps)


def _dense(units, use_tp, mode, **kw):
    if use_tp:
        from ....parallel.tensor_parallel import ParallelDense
        return ParallelDense(units, parallel_mode=mode, use_bias=False,
                             flatten=False, **kw)
    return nn.Dense(units, use_bias=False, flatten=False, **kw)


class LlamaAttention(HybridBlock):
    def __init__(self, cfg, **kwargs):
        super().__init__(**kwargs)
        self.cfg = cfg
        h, kvh, d = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
        with self.name_scope():
            self.q_proj = _dense(h * d, cfg.tensor_parallel, "column")
            self.k_proj = _dense(kvh * d, cfg.tensor_parallel, "column")
            self.v_proj = _dense(kvh * d, cfg.tensor_parallel, "column")
            self.o_proj = _dense(cfg.hidden_size, cfg.tensor_parallel, "row")

    def hybrid_forward(self, F, x):
        import jax
        import jax.numpy as jnp
        from ....ndarray.ndarray import apply_nary
        from ....ops.flash_attention import flash_attention
        cfg = self.cfg
        b, t = x.shape[0], x.shape[1]
        h, kvh, d = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
        q = self.q_proj(x)
        k = self.k_proj(x)
        v = self.v_proj(x)
        theta = cfg.rope_theta

        def rope_and_shape(qd, kd, vd, repeat_kv=True):
            qd = qd.reshape(b, t, h, d).transpose(0, 2, 1, 3)
            kd = kd.reshape(b, t, kvh, d).transpose(0, 2, 1, 3)
            vd = vd.reshape(b, t, kvh, d).transpose(0, 2, 1, 3)
            # rotary embeddings
            pos = jnp.arange(t)
            freqs = theta ** (-jnp.arange(0, d, 2) / d)
            ang = pos[:, None] * freqs[None, :]           # (t, d/2)
            cos, sin = jnp.cos(ang), jnp.sin(ang)

            qd = _rot_interleaved(qd, cos, sin)
            kd = _rot_interleaved(kd, cos, sin)
            if repeat_kv:
                # GQA: repeat kv heads (the ulysses path defers this until
                # after its all-to-all so the wire carries only true kv)
                rep = h // kvh
                kd = jnp.repeat(kd, rep, axis=1)
                vd = jnp.repeat(vd, rep, axis=1)
            return qd, kd, vd

        # Context parallelism is a COMPILED feature: ring attention's
        # shard_map only composes with jit tracing (hybridize /
        # DataParallelTrainer / dryrun) or eager inference — the eager
        # imperative tape records ops under jax.vjp, where cross-device
        # resharding is illegal. Under an eager recorded forward we fall
        # back to local flash attention (numerically identical; just not
        # sequence-sharded).
        from .... import _tape
        use_ring = False
        mesh = None
        if cfg.context_parallel:
            from ....parallel import current_mesh
            mesh = current_mesh()
            in_jit_trace = _tape._STATE.trace_depth > 0
            eager_infer = not _tape.is_recording()
            use_ring = (mesh is not None and "sp" in mesh.shape
                        and (in_jit_trace or eager_infer))

        def attn(qd, kd, vd):
            # cfg.context_parallel selects the CP scheme (SURVEY §5.7
            # lists both): "ulysses" = 4 all-to-alls per layer (q/k/v
            # scatter + out gather), bandwidth ~4x activation; ring =
            # S-1 neighbour K/V block hops
            ulysses = use_ring and self.cfg.context_parallel == "ulysses"
            qd, kd, vd = rope_and_shape(qd, kd, vd, repeat_kv=not ulysses)
            if ulysses:
                from ....parallel.ulysses import ulysses_attention
                o = ulysses_attention(qd, kd, vd, mesh, axis_name="sp",
                                      causal=True)
            elif use_ring:
                from ....parallel.ring_attention import ring_attention
                o = ring_attention(qd, kd, vd, mesh, axis_name="sp",
                                   causal=True)
            else:
                o = flash_attention(qd, kd, vd, causal=True)
            if hasattr(o, "data"):
                o = o.data
            return o.transpose(0, 2, 1, 3).reshape(b, t, h * d)

        out = apply_nary(attn, [q, k, v], name="llama_attention")
        return self.o_proj(out)


class LlamaMLP(HybridBlock):
    """SwiGLU feed-forward."""

    def __init__(self, cfg, **kwargs):
        super().__init__(**kwargs)
        with self.name_scope():
            self.gate_proj = _dense(cfg.intermediate_size,
                                    cfg.tensor_parallel, "column")
            self.up_proj = _dense(cfg.intermediate_size,
                                  cfg.tensor_parallel, "column")
            self.down_proj = _dense(cfg.hidden_size,
                                    cfg.tensor_parallel, "row")

    def hybrid_forward(self, F, x):
        import jax
        from ....ndarray.ndarray import apply_nary
        gate = self.gate_proj(x)
        up = self.up_proj(x)

        def fn(g, u):
            return jax.nn.silu(g) * u

        return self.down_proj(apply_nary(fn, [gate, up], name="swiglu"))


class LlamaLayer(HybridBlock):
    def __init__(self, cfg, **kwargs):
        super().__init__(**kwargs)
        with self.name_scope():
            self.input_norm = RMSNorm(cfg.hidden_size, cfg.rms_eps)
            self.attention = LlamaAttention(cfg)
            self.post_norm = RMSNorm(cfg.hidden_size, cfg.rms_eps)
            self.mlp = LlamaMLP(cfg)

    def hybrid_forward(self, F, x):
        x = x + self.attention(self.input_norm(x))
        return x + self.mlp(self.post_norm(x))


class LlamaModel(HybridBlock):
    def __init__(self, cfg, **kwargs):
        super().__init__(**kwargs)
        self.cfg = cfg
        with self.name_scope():
            self.embed = nn.Embedding(cfg.vocab_size, cfg.hidden_size)
            self.layers = nn.HybridSequential()
            for _ in range(cfg.num_layers):
                self.layers.add(LlamaLayer(cfg))
            self.norm = RMSNorm(cfg.hidden_size, cfg.rms_eps)

    def hybrid_forward(self, F, tokens):
        x = self.embed(tokens)
        for layer in self.layers:
            x = layer(x)
        return self.norm(x)

    def remat(self, active=True):
        """Per-decoder-layer jax.checkpoint: keep only layer-boundary
        activations in HBM, recompute interiors in backward (the long-
        context memory schedule; composes with the TP/CP shardings)."""
        for layer in self.layers:
            layer.hybridize(active, remat=active)


class LlamaForCausalLM(HybridBlock):
    def __init__(self, cfg, **kwargs):
        super().__init__(**kwargs)
        self.cfg = cfg
        with self.name_scope():
            self.model = LlamaModel(cfg)
            self.lm_head = None if cfg.tie_embeddings else \
                _dense(cfg.vocab_size, cfg.tensor_parallel, "column")

    def hybrid_forward(self, F, tokens):
        import jax.numpy as jnp
        from ....ndarray.ndarray import apply_nary
        x = self.model(tokens)
        if self.lm_head is not None:
            return self.lm_head(x)
        w = self.model.embed.weight.data()

        def fn(d, emb):
            return d @ emb.T

        return apply_nary(fn, [x, w], name="tied_lm_head")

    def fused_ce_loss(self, tokens, targets, block=2048,
                      ignore_index=None):
        """Per-token CE via the blocked fused head
        (ops/blocked_cross_entropy.py): the (B, L, V) logit tensor is
        never materialized — O(B*L*block) activation memory, the
        long-context memory lever on the loss side (remat covers the
        trunk side). Single-path head only: with a column-TP lm_head the
        vocab is sharded and the blocked logsumexp would need a psum per
        block — use the standard logits path there."""
        from ....base import MXNetError
        from ....ndarray.ndarray import apply_nary
        from ....ops.blocked_cross_entropy import \
            fused_linear_cross_entropy as f
        if self.cfg.tensor_parallel:
            raise MXNetError("fused_ce_loss: vocab is column-sharded "
                             "under tensor_parallel; use the logits path")
        import jax.numpy as jnp
        x = self.model(tokens)
        w = (self.model.embed.weight.data() if self.lm_head is None
             else self.lm_head.weight.data())

        def fn(h, wv, t):
            d = h.shape[-1]
            # both storage layouts are (V, d): lm_head Dense and the tied
            # embedding — transpose unconditionally (a layout change
            # fails loudly in the matmul instead of silently sniffing)
            loss = f(h.reshape(-1, d), wv.T,
                     t.reshape(-1).astype(jnp.int32), block=block,
                     ignore_index=ignore_index)
            return loss.reshape(h.shape[:-1])

        return apply_nary(fn, [x, w, targets], name="fused_ce_loss")

    # ------------------------------------------------------------------
    # KV-cache autoregressive decoding
    # ------------------------------------------------------------------
    def _decode_params(self):
        m = self.model
        layers = []
        for layer in m.layers:
            a, f = layer.attention, layer.mlp
            layers.append((layer.input_norm.weight.data().data,
                           a.q_proj.weight.data().data,
                           a.k_proj.weight.data().data,
                           a.v_proj.weight.data().data,
                           a.o_proj.weight.data().data,
                           layer.post_norm.weight.data().data,
                           f.gate_proj.weight.data().data,
                           f.up_proj.weight.data().data,
                           f.down_proj.weight.data().data))
        head = None if self.lm_head is None \
            else self.lm_head.weight.data().data
        return (m.embed.weight.data().data, m.norm.weight.data().data,
                head, layers)

    def decode_weights(self):
        """Public decode-weight pytree: (embed, final_norm, lm_head|None,
        [per-layer (in_norm, q, k, v, o, post_norm, gate, up, down)]) as
        jax arrays.  The serving engine (``mxnet_tpu.serving``) and
        ``generate()`` both consume this — weights are jit ARGUMENTS, never
        baked into executables as constants."""
        return self._decode_params()

    def generate(self, tokens, max_new_tokens, temperature=0.0, seed=0):
        """Autoregressive decode with per-layer KV caches: ONE jitted
        lax.scan over prefill+generation (static shapes — cache length is
        prefix+max_new), a single cache-row dynamic_update_slice per layer
        per step. The inference path the reference era served via repeated
        full forwards; here the step is O(T) attention against the cache
        instead of O(T^2) recompute. Greedy at temperature=0, else
        categorical sampling from logits/temperature.

        tokens: (B, T_prefix) int NDArray; returns (B, T_prefix +
        max_new_tokens) int32 NDArray.
        """
        import jax
        import jax.numpy as jnp
        from jax import lax
        from ....ndarray.ndarray import NDArray, from_jax

        cfg = self.cfg
        if cfg.tensor_parallel:
            raise MXNetError("generate() runs the single-chip decode path; "
                             "TP-sharded models serve through forward()")
        toks = tokens.data.astype(jnp.int32) if isinstance(tokens, NDArray) \
            else jnp.asarray(tokens, jnp.int32)
        b, t_prefix = toks.shape
        if t_prefix == 0:
            raise MXNetError("generate() needs at least one prefix token")
        total = t_prefix + int(max_new_tokens)
        h, kvh, d = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
        params = self._decode_params()   # pytree: passed as a jit ARGUMENT
        # (weights must not bake into the executable as constants), and the
        # compiled scan is cached per shape/temperature signature
        n_layers = len(params[3])
        theta = cfg.rope_theta
        temp = float(temperature)
        eps = cfg.rms_eps

        def run(params, toks, key):
            emb, norm_w, head_w, layers = params
            freqs = theta ** (-jnp.arange(0, d, 2) / d)

            def step(carry, xs):
                caches_k, caches_v, prev, key = carry
                i, forced = xs
                tok = jnp.where(i < t_prefix, forced, prev)    # (B,)
                x = emb[tok]                                   # (B, hidden)
                pos_mask = (jnp.arange(total) <= i)            # (total,)
                new_k, new_v = [], []
                for li, (in_w, qw, kw, vw, ow, po_w, gw, uw, dw) in \
                        enumerate(layers):
                    hh = _rms(x, in_w, eps)
                    q = (hh @ qw.T).reshape(b, h, d)
                    k = (hh @ kw.T).reshape(b, kvh, d)
                    v = (hh @ vw.T).reshape(b, kvh, d)
                    ang = i * freqs
                    cos, sin = jnp.cos(ang), jnp.sin(ang)
                    q = _rot_interleaved(q, cos, sin)
                    k = _rot_interleaved(k, cos, sin)
                    ck = lax.dynamic_update_slice(
                        caches_k[li], k[:, :, None, :], (0, 0, i, 0))
                    cv = lax.dynamic_update_slice(
                        caches_v[li], v[:, :, None, :], (0, 0, i, 0))
                    new_k.append(ck)
                    new_v.append(cv)
                    valid = jnp.broadcast_to(pos_mask[None, :], (b, total))
                    o = _cache_attention(q, ck, cv, valid,
                                         1.0 / math.sqrt(d))
                    x = x + o @ ow.T
                    y = _rms(x, po_w, eps)
                    x = x + (jax.nn.silu(y @ gw.T) * (y @ uw.T)) @ dw.T
                logits = _rms(x, norm_w, eps) @ (emb.T if head_w is None
                                                 else head_w.T)
                if temp == 0.0:
                    nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                else:
                    key, sub = jax.random.split(key)
                    nxt = jax.random.categorical(
                        sub, logits.astype(jnp.float32) / temp,
                        axis=-1).astype(jnp.int32)
                return (new_k, new_v, nxt, key), nxt

            caches_k = [jnp.zeros((b, kvh, total, d), emb.dtype)
                        for _ in range(n_layers)]
            caches_v = [jnp.zeros((b, kvh, total, d), emb.dtype)
                        for _ in range(n_layers)]
            forced = jnp.concatenate(
                [toks, jnp.zeros((b, total - t_prefix), jnp.int32)], axis=1)
            init = (caches_k, caches_v, jnp.zeros((b,), jnp.int32), key)
            _, outs = lax.scan(step, init,
                               (jnp.arange(total), forced.T))
            # outs[i] = next-token prediction AFTER consuming position i;
            # generated tokens are outs[t_prefix-1 : total-1]
            gen = outs[t_prefix - 1:total - 1].T        # (B, max_new)
            return jnp.concatenate([toks, gen], axis=1)

        sig = (b, t_prefix, total, temp)
        cache = getattr(self, "_gen_jit", None)
        if cache is None:
            cache = self._gen_jit = {}
        if sig not in cache:
            cache[sig] = jax.jit(run)
        return from_jax(cache[sig](params, toks, jax.random.key(seed)))


def llama3_8b(**overrides):
    """Llama-3-8B geometry (BASELINE stretch config)."""
    return LlamaForCausalLM(LlamaConfig(**overrides))


def llama_tiny(**overrides):
    """Tiny config for tests / dryruns."""
    kw = dict(vocab_size=256, hidden_size=64, intermediate_size=128,
              num_layers=2, num_heads=4, num_kv_heads=2, max_seq_len=128)
    kw.update(overrides)
    return LlamaForCausalLM(LlamaConfig(**kw))
