"""AOT-compiled bucketed inference engine for Llama-family decoders.

Serving can't afford a retrace mid-traffic (PR 1's retrace detector
exists because one recompile stalls every request on the chip), so the
engine AOT-compiles TWO graph families at warmup and only ever looks
them up afterwards:

- ``prefill[bucket]``: a full causal forward over a prompt padded to a
  power-of-two sequence bucket, writing K/V (unrepeated GQA heads) into
  the sequence's pool blocks and sampling the first generated token from
  the last valid position's logits.
- ``decode[n_blocks]``: ONE token for the whole fixed-size batch against
  the paged KV cache — block-table gather, per-row position mask, the
  shared ``llama._cache_attention`` math (bitwise the full forward, see
  the decode-parity gate in tests/test_serving.py), current K/V
  scattered into the pool before attending, next token sampled in-graph.

Both families take the KV pools as DONATED arguments (the PR 6
``step_multi`` carry discipline): the cache is updated functionally and
swapped on the host, never copied.  Weights are jit arguments, never
baked constants.  The compile cache is keyed like PR 1's retrace
detector — every (kind, shape-signature) miss is counted, and
``stats["compiles_after_warmup"]`` staying 0 under traffic is a tier-1
assertion.

int8 serving: pass ``quantize="int8"`` (+ calibration batches) and the
engine routes the net through ``contrib.quantization.quantize_net`` —
the projection weights become per-channel int8 with the calibrated
activation scales, and the engine's matmuls mirror ``QuantizedDense``
op-for-op (int32 accumulation is exact, so decode parity survives
quantization bit-for-bit against the quantized net's own forward).

ISSUE 12 adds a third graph family for the serving FRONT-END
(``mxnet_tpu.serving.frontend``):

- ``chunk[n_blocks]``: a PACKED continuation prefill — up to
  ``max_batch`` rows, each a chunk of up to ``MXTPU_PREFILL_CHUNK``
  prompt tokens starting at an arbitrary position, attending to that
  row's already-cached K/V through its block table (offset-causal
  mask).  One dispatch admits several queued prompts of a boundary
  (chunked/batched prefill) AND computes only the un-cached suffix of
  a prompt whose prefix the :class:`~.frontend.PrefixCache` already
  holds.  The chunk math mirrors the cold prefill's flash path
  op-for-op (same blockwise online-softmax, same mask constant), so
  the K/V it writes — and therefore every later decode logit — is
  BITWISE the cold path's (tests/test_serving_frontend.py).
- ``cow``: a one-block pool copy, the device half of the kv-cache's
  copy-on-write fork (a shared block is copied before its first
  write; every other holder keeps the original bits).

Both are compiled at warmup like the rest; ``compiles_after_warmup``
still gates zero retraces.  Replicas behind one
:class:`~.frontend.Router` pass a shared ``compile_cache`` so the
fleet pays each graph compile once.

ISSUE 17 adds the SPECULATIVE graph family:

- ``verify[(k, n_blocks)]``: ``k`` (power-of-two bucket) decode steps
  UNROLLED inside one dispatch — step ``w`` feeds the row's ``w``-th
  token (the last committed token, then the draft continuation) at
  position ``pos + w``, writes its K/V through the block table, and
  argmaxes the next token; the functional kp/vp threading makes step
  ``w``'s writes visible to step ``w+1``.  Each unrolled step is the
  ``decode`` body op-for-op (same projections, same
  ``_cache_attention``/paged-attention routing, same scatter), so the
  greedy token at every ACCEPTED position is bitwise the plain decode
  path's — the acceptance gate is exact token equality, never a
  tolerance (the PR 7 decode-parity contract extended through the
  multi-step seam, device-resident like PR 6's ``step_multi``).
  Rows with fewer real tokens than the bucket mask their dead steps
  into the null block; a row with ONE token is exactly a plain decode
  row, which is how mixed draft/no-draft batches share the dispatch.
  Speculation is greedy-only (temperature 0) — acceptance compares
  argmaxes, so sampled decoding keeps the plain path.

``MXTPU_PAGED_ATTN=1`` reroutes the decode/verify cache attention
through ``ops.paged_attention.paged_decode_attention`` — whose XLA
fallback is the inline gather + ``_cache_attention`` verbatim (bitwise
on CPU; the Pallas gather-by-block-table kernel engages on TPU hosts).
"""
from __future__ import annotations

import math
import os

import numpy as _np

from ..base import MXNetError, NotSupportedError
from .. import telemetry as _telem
from ..telemetry import tracing as _trace
from .kv_cache import PagedKVCache

__all__ = ["InferenceEngine", "next_bucket"]


def _env_int(name, default):
    try:
        return int(os.environ.get(name, default))
    except ValueError:
        return default


def next_bucket(n, buckets):
    """Smallest bucket >= n, or None when n exceeds every bucket."""
    for b in buckets:
        if n <= b:
            return b
    return None


class InferenceEngine:
    """Compiled serving engine over one ``LlamaForCausalLM``.

    Parameters
    ----------
    net : initialized LlamaForCausalLM (run one forward first so shapes
        are materialized).  With ``quantize="int8"`` the net's Dense
        projections are swapped for int8 twins IN PLACE via
        ``contrib.quantization.quantize_net``.
    max_batch : decode slots (>= 2; the compiled decode batch is fixed).
    block_size : KV-cache block size in tokens (power of two).
    max_context : longest supported sequence (rounded down to a multiple
        of ``block_size``); prefill/decode buckets are the powers of two
        in [block_size, max_context].
    temperature / top_k / seed : in-graph sampling config (greedy at
        temperature 0; otherwise top-k categorical when top_k > 0, full
        categorical when 0).
    mesh : a ``parallel.MeshConfig`` (or spec string).  tp > 1 serves
        the model SHARDED over a tp submesh (ISSUE 18): extracted
        weights are placed at rest with the
        ``tensor_parallel.llama_engine_specs`` megatron table, the
        paged KV pools are sharded on the kv-head axis, and every
        graph family compiles against the sharded layouts (the mesh
        spec rides in the compile-cache signature).  pp > 1 still
        raises the typed ``NotSupportedError``.  None reads
        ``MXTPU_SERVE_TP`` (default unset = the single-chip engine,
        bitwise-inert).
    kv_cache : an existing ``PagedKVCache`` to ADOPT instead of
        building one (ISSUE 18 disaggregated serving: prefill and
        decode replicas share one physical pool so a block handoff
        transfers ownership, not bytes).  Geometry must match this
        engine's net and ``block_size``.
    prefill_chunk : chunk bucket in tokens (multiple of block_size) for
        the packed continuation-prefill family; 0/None reads
        ``MXTPU_PREFILL_CHUNK`` (default off).
    prefix_cache : True builds a ``frontend.PrefixCache`` over this
        engine's KV pool; None reads ``MXTPU_PREFIX_CACHE``.
    compile_cache : dict shared across replicas of a ``frontend.Router``
        so the fleet pays each graph compile once (signatures carry the
        pool geometry, so mismatched engines never collide).
    spec_decode : True compiles the speculative ``verify`` graph family
        at warmup (greedy-only — requires temperature 0); None reads
        ``MXTPU_SPEC_DECODE`` (default off: no extra warmup compiles,
        bitwise the PR 7 engine).
    spec_k : max draft tokens scored per verify dispatch (>= 1); the
        compiled widths are the power-of-two buckets covering
        ``spec_k + 1`` fed tokens.  None reads ``MXTPU_SPEC_K``
        (default 4).
    paged_attn : True routes decode/verify cache attention through
        ``ops.paged_attention`` (Pallas gather-by-block-table on TPU;
        bitwise-identical XLA fallback elsewhere); None reads
        ``MXTPU_PAGED_ATTN`` (default off = the inline gather).
    kv_dtype : KV-cache STORAGE precision (ISSUE 20): ``"fp8"`` stores
        e4m3 codes with per-token-row amax scales (quantize-on-write /
        dequantize-in-attention threaded through every graph family —
        the attention math itself stays f32, so drift is bounded by
        the storage rounding alone); ``"bf16"`` stores bfloat16;
        ``"fp32"``/None-resolved-empty is today's engine, bitwise.
        None reads ``MXTPU_KV_DTYPE`` (default unset).  Prefill's OWN
        attention reads the fresh f32 K/V, so the first generated
        token never drifts; decode/verify/chunk read the pool.
    """

    def __init__(self, net, max_batch=None, block_size=None,
                 num_blocks=None, max_context=None, temperature=0.0,
                 top_k=0, seed=0, quantize=None, calib_data=None,
                 num_calib_batches=10, mesh=None, prefill_chunk=None,
                 prefix_cache=None, compile_cache=None,
                 spec_decode=None, spec_k=None, paged_attn=None,
                 kv_cache=None, kv_dtype=None):
        import jax
        import jax.numpy as jnp
        from ..ops import quant_kv as _qkv
        from ..parallel.mesh import MeshConfig
        cfg = net.cfg
        if cfg.tensor_parallel:
            raise NotSupportedError(
                "InferenceEngine extracts and places its own weights "
                "(pass mesh=MeshConfig(tp=N) for sharded serving); "
                "structurally tensor_parallel nets serve via forward()")
        if quantize not in (None, "int8"):
            raise MXNetError(f"quantize={quantize!r}: only int8 weight "
                             "quantization is supported")
        if mesh is None:
            tp_env = _env_int("MXTPU_SERVE_TP", 0)
            if tp_env > 1:
                mesh = MeshConfig(tp=tp_env)
        if isinstance(mesh, str):
            mesh = MeshConfig.from_spec(mesh)
        self.mesh_config = mesh if mesh is not None else MeshConfig()
        if self.mesh_config.pp > 1:
            raise NotSupportedError(
                f"mesh {self.mesh_config.describe()!r}: serving over "
                "the pp axis is still unsupported (tp submeshes serve "
                "since ISSUE 18; pipeline-staged serving is a later "
                "follow-up)")
        self.tp = self.mesh_config.tp
        self._mesh = None
        if self.tp > 1:
            if quantize is not None:
                raise NotSupportedError(
                    "int8 serving on a tp submesh is not supported; "
                    "serve quantized nets on single-chip replicas")
            need = self.mesh_config.dp * self.tp * self.mesh_config.pp
            ndev = len(jax.devices())
            if need > ndev:
                raise MXNetError(
                    f"mesh {self.mesh_config.describe()!r} needs "
                    f"{need} devices; only {ndev} visible")
            if cfg.num_heads % self.tp or cfg.num_kv_heads % self.tp:
                raise MXNetError(
                    f"tp={self.tp} must divide num_heads "
                    f"{cfg.num_heads} and num_kv_heads "
                    f"{cfg.num_kv_heads}")
            if cfg.intermediate_size % self.tp:
                raise MXNetError(
                    f"tp={self.tp} must divide intermediate_size "
                    f"{cfg.intermediate_size}")
            self._mesh = self.mesh_config.build()
        self.net = net
        self.cfg = cfg
        self.max_batch = max(2, _env_int("MXTPU_SERVE_MAX_BATCH", 4)
                             if max_batch is None else int(max_batch))
        bs = _env_int("MXTPU_SERVE_BLOCK", 16) if block_size is None \
            else int(block_size)
        mc = max_context if max_context is not None else \
            min(cfg.max_seq_len, _env_int("MXTPU_SERVE_MAX_CONTEXT", 1024))
        mc = (mc // bs) * bs
        if mc < bs:
            raise MXNetError(f"max_context {mc} < block_size {bs}")
        self.block_size = bs
        self.max_context = mc
        # shape buckets: powers of two in [block_size, max_context] —
        # each bucket is one compiled graph, so traffic of ANY length
        # mix runs on this fixed, warmup-compiled set
        self.buckets = []
        b = bs
        while b <= mc:
            self.buckets.append(b)
            b *= 2
        if num_blocks is None:
            num_blocks = 1 + self.max_batch * (mc // bs)
        self.quantized = False
        if quantize == "int8":
            self._quantize_in_place(net, calib_data, num_calib_batches)
        # KV storage precision (ISSUE 20): resolved ONCE here; every
        # graph builder branches on it at trace time, so an unset knob
        # compiles exactly today's graphs (the bitwise kill switch)
        self.kv_dtype = _qkv.resolve_kv_dtype(kv_dtype)
        self._kv_fp8 = _qkv.kv_has_scales(self.kv_dtype)
        self.params = self._extract_weights(net)
        if self._mesh is not None:
            self.params = self._shard_params(self.params)
        if kv_cache is not None:
            # disaggregated serving (ISSUE 18): prefill and decode
            # replicas ADOPT one physical pool — the block handoff is
            # an ownership transfer through the CoW refcounts, never a
            # copy.  Geometry must match or the compiled graphs would
            # gather garbage.
            if (kv_cache.num_layers != cfg.num_layers
                    or kv_cache.num_kv_heads != cfg.num_kv_heads
                    or kv_cache.head_dim != cfg.head_dim
                    or kv_cache.block_size != bs
                    or kv_cache.kv_dtype != self.kv_dtype
                    or (self.kv_dtype is None and
                        kv_cache.dtype != self.params["embed"].dtype)):
                raise MXNetError(
                    "kv_cache geometry mismatch: shared pool is "
                    f"(layers={kv_cache.num_layers}, "
                    f"kvh={kv_cache.num_kv_heads}, "
                    f"hd={kv_cache.head_dim}, "
                    f"bs={kv_cache.block_size}, "
                    f"kv_dtype={kv_cache.kv_dtype or 'fp32'}) vs this "
                    f"engine's (layers={cfg.num_layers}, "
                    f"kvh={cfg.num_kv_heads}, hd={cfg.head_dim}, "
                    f"bs={bs}, kv_dtype={self.kv_dtype or 'fp32'})")
            self.cache = kv_cache
            self.cache_shared = True
        else:
            pool_sharding = None
            if self._mesh is not None:
                from jax.sharding import NamedSharding, PartitionSpec
                from ..parallel.mesh import AXIS_TP
                pool_sharding = NamedSharding(
                    self._mesh,
                    PartitionSpec(None, None, None, AXIS_TP, None))
            self.cache = PagedKVCache(
                cfg.num_layers, cfg.num_kv_heads, cfg.head_dim,
                num_blocks=num_blocks, block_size=bs,
                max_batch=self.max_batch,
                dtype=self.params["embed"].dtype,
                sharding=pool_sharding,
                kv_dtype=self.kv_dtype or "fp32")
            self.cache_shared = False
        self.temperature = float(temperature)
        self.top_k = int(top_k)
        self._base_key = jax.random.key(seed)
        # compile cache: pass one dict to every replica of a Router and
        # the whole fleet pays each (kind, size) compile exactly once —
        # executables close over shapes only (weights/pools are jit
        # ARGUMENTS), so replicas with identical config share freely
        self._compiled = {} if compile_cache is None else compile_cache
        self._warmed = False
        # chunked/batched prefill (ISSUE 12): chunk bucket in tokens;
        # 0 disables the family (no extra warmup compiles)
        pc = _env_int("MXTPU_PREFILL_CHUNK", 0) if prefill_chunk is None \
            else int(prefill_chunk)
        if pc < 0 or (pc and pc % bs):
            raise MXNetError(f"prefill_chunk {pc} must be a positive "
                             f"multiple of block_size {bs} (or 0=off)")
        self.prefill_chunk = min(pc, mc)
        # copy-on-write prefix cache: True builds one, an instance is
        # adopted, None reads MXTPU_PREFIX_CACHE (default off so the
        # cold engine's block accounting is exactly PR 7's)
        if prefix_cache is None:
            prefix_cache = os.environ.get(
                "MXTPU_PREFIX_CACHE", "0") not in ("", "0")
        if prefix_cache is True:
            from .frontend.prefix_cache import PrefixCache
            self.prefix_cache = PrefixCache(self.cache)
        else:
            self.prefix_cache = prefix_cache or None
        # speculative decoding (ISSUE 17): kill switch default-off so
        # the cold engine compiles nothing extra and is bitwise PR 7's
        if spec_decode is None:
            spec_decode = os.environ.get(
                "MXTPU_SPEC_DECODE", "0") not in ("", "0")
        self.spec_decode = bool(spec_decode)
        self.spec_k = _env_int("MXTPU_SPEC_K", 4) if spec_k is None \
            else int(spec_k)
        if self.spec_k < 1:
            raise MXNetError(f"spec_k {self.spec_k} must be >= 1")
        if self.spec_decode and self.temperature != 0.0:
            raise NotSupportedError(
                "speculative decoding is greedy-only (acceptance "
                "compares argmaxes bitwise); serve temperature > 0 "
                "with MXTPU_SPEC_DECODE=0")
        # paged decode-attention kernel routing (ISSUE 17): default off
        # keeps the inline gather; on CPU the op's fallback is that
        # gather verbatim, so the knob is bitwise-inert off-TPU
        if paged_attn is None:
            paged_attn = os.environ.get(
                "MXTPU_PAGED_ATTN", "0") not in ("", "0")
        self.paged_attn = bool(paged_attn)
        self.stats = {"compiles": 0, "compiles_after_warmup": 0,
                      "prefill_calls": 0, "decode_calls": 0,
                      "chunk_prefill_calls": 0,
                      "prompt_tokens_computed": 0,
                      "verify_calls": 0, "draft_tokens_scored": 0}

    # -- weights ---------------------------------------------------------

    def _quantize_in_place(self, net, calib_data, num_calib_batches):
        from ..contrib.quantization import QuantizedDense, quantize_net
        has_q = any(isinstance(m, QuantizedDense) for m in
                    self._walk(net))
        if not has_q:
            if calib_data is None:
                raise MXNetError("quantize='int8' needs calib_data "
                                 "(token batches for PTQ calibration)")
            # calibration hooks pull activations host-side, which is
            # illegal inside a jitted forward — run the calibration
            # forwards eagerly, then restore hybridization
            was_active = getattr(net, "_active", False)
            if was_active:
                net.hybridize(False)
            try:
                quantize_net(net, calib_data=calib_data,
                             num_calib_batches=num_calib_batches)
            finally:
                if was_active:
                    net.hybridize(True)
        self.quantized = True

    @staticmethod
    def _walk(block):
        yield block
        for child in block._children.values():
            yield from InferenceEngine._walk(child)

    def _proj_params(self, layer):
        """One projection as a tagged dict: {'w'} fp32 or
        {'qw','ws','as'} int8 (QuantizedDense twins)."""
        import jax.numpy as jnp
        from ..contrib.quantization import QuantizedDense
        if isinstance(layer, QuantizedDense):
            return {"qw": layer.quantized_weight,
                    "ws": layer.weight_scale.astype(jnp.float32),
                    "as": jnp.float32(layer.act_scale)}
        return {"w": layer.weight.data().data}

    def _extract_weights(self, net):
        m = net.model
        layers = []
        for layer in m.layers:
            a, f = layer.attention, layer.mlp
            layers.append({
                "in_norm": layer.input_norm.weight.data().data,
                "q": self._proj_params(a.q_proj),
                "k": self._proj_params(a.k_proj),
                "v": self._proj_params(a.v_proj),
                "o": self._proj_params(a.o_proj),
                "post_norm": layer.post_norm.weight.data().data,
                "gate": self._proj_params(f.gate_proj),
                "up": self._proj_params(f.up_proj),
                "down": self._proj_params(f.down_proj),
            })
        params = {"embed": m.embed.weight.data().data,
                  "norm": m.norm.weight.data().data,
                  "layers": layers}
        if net.lm_head is not None:
            params["head"] = self._proj_params(net.lm_head)
        return params

    # -- tp sharding (ISSUE 18) ------------------------------------------

    def _shard_params(self, params):
        """Place the extracted weights on the tp submesh AT REST:
        column-parallel projections (q/k/v/gate/up) shard their output
        features, row-parallel ones (o/down) their input features —
        the ``tensor_parallel.llama_engine_specs`` megatron table —
        and embeddings/norms/head replicate.  Placement happens once
        here; the AOT-lowered executables bake these input shardings
        in, so a drifted layout fails loudly instead of resharding
        silently per dispatch."""
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P
        from ..parallel.tensor_parallel import llama_engine_specs
        mesh = self._mesh
        specs = llama_engine_specs()

        def put(w, spec):
            return jax.device_put(w, NamedSharding(mesh, spec))

        layers = []
        for lp in params["layers"]:
            out = {"in_norm": put(lp["in_norm"], P(None)),
                   "post_norm": put(lp["post_norm"], P(None))}
            for name in ("q", "k", "v", "o", "gate", "up", "down"):
                out[name] = {"w": put(lp[name]["w"], specs[name])}
            layers.append(out)
        sharded = {"embed": put(params["embed"], P(None, None)),
                   "norm": put(params["norm"], P(None)),
                   "layers": layers}
        if "head" in params:
            sharded["head"] = {"w": put(params["head"]["w"],
                                        P(None, None))}
        return sharded

    def _row_proj(self, x, p):
        """The o_proj/down_proj matmul on a tp submesh.  The incoming
        activation is sharded on its feature axis (it is the paired
        column-parallel outputs); plain megatron would contract the
        SPLIT axis per shard and all-reduce the partials — but that
        reassociates the fp32 K-sum and is measurably not bitwise the
        unsharded gemm on this mesh.  Instead both the activation and
        the (in-features-sharded) row weight are constrained replicated
        IN-GRAPH: XLA's sharding algebra inserts all-gathers (pure
        data movement, bit-preserving) and the gemm contracts the full
        K axis exactly like the single-chip engine — the decode-parity
        contract survives sharding bit-for-bit while the weights stay
        sharded at rest (the HBM win) and every upstream matmul stays
        genuinely column-parallel."""
        if self._mesh is None:
            return self._proj(x, p)
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        rep_x = NamedSharding(self._mesh, P(*([None] * x.ndim)))
        rep_w = NamedSharding(self._mesh, P(None, None))
        x = jax.lax.with_sharding_constraint(x, rep_x)
        w = jax.lax.with_sharding_constraint(p["w"], rep_w)
        return jnp.matmul(x, w.T)

    def _gather_layer(self, lp):
        """Replicate one decode layer's projection weights in-graph.
        Prefill's big gemms stay genuinely column-parallel (full-K
        contractions per output column are bitwise-safe), but decode's
        (B, hid) gemvs are small enough that the partitioner regroups
        them — so the decode/verify graphs gather weights instead
        (decode is bandwidth-bound; the all-gather is bit-preserving
        data movement and the gemv then matches the single-chip
        engine exactly).  Weights stay sharded at rest either way."""
        if self._mesh is None:
            return lp
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P
        rep = NamedSharding(self._mesh, P(None, None))
        out = dict(lp)
        for name in ("q", "k", "v", "o", "gate", "up", "down"):
            p = dict(lp[name])
            p["w"] = jax.lax.with_sharding_constraint(p["w"], rep)
            out[name] = p
        return out

    def _gather_cache(self, ck, cv):
        """Replicate the cache slices a decode step attends over.
        ``_cache_attention`` merges the (sharded) head axis into a
        flat batch axis; left sharded, the partitioner's regrouping of
        that contraction drifts ~1e-7 from the single-chip recurrence.
        An in-graph all-gather is pure data movement, so the attention
        math stays bitwise the unsharded engine's."""
        if self._mesh is None:
            return ck, cv
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P
        rep = NamedSharding(self._mesh, P(*([None] * ck.ndim)))
        return (jax.lax.with_sharding_constraint(ck, rep),
                jax.lax.with_sharding_constraint(cv, rep))

    def _shard_pools(self, kp, vp):
        """Constrain returned pools back to the at-rest kv-head
        sharding so the donated round-trip hands the next dispatch the
        layout its executable was lowered against."""
        if self._mesh is None:
            return kp, vp
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P
        from ..parallel.mesh import AXIS_TP
        s = NamedSharding(self._mesh, P(None, None, None, AXIS_TP, None))
        return (jax.lax.with_sharding_constraint(kp, s),
                jax.lax.with_sharding_constraint(vp, s))

    def _shard_scales(self, ks, vs):
        """fp8 scale rows have no kv-head axis (one scalar per token
        row, shared across heads) — they replicate on the submesh."""
        if self._mesh is None:
            return ks, vs
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P
        s = NamedSharding(self._mesh, P(None, None, None))
        return (jax.lax.with_sharding_constraint(ks, s),
                jax.lax.with_sharding_constraint(vs, s))

    # -- graph building --------------------------------------------------

    @staticmethod
    def _proj(x, p):
        """Dense matmul mirroring the block forwards op-for-op:
        fp32 = FullyConnected's ``x @ w.T``; int8 = QuantizedDense's
        round/clip -> int8 dot_general(int32 accum) -> rescale."""
        import jax.numpy as jnp
        from jax import lax
        if "qw" in p:
            from ..ops.quant_matmul import quantize_rtn_int8
            lead = x.shape[:-1]
            flat = x.reshape(-1, x.shape[-1])
            qx = quantize_rtn_int8(flat, p["as"])
            acc = lax.dot_general(qx, p["qw"], (((1,), (1,)), ((), ())),
                                  preferred_element_type=jnp.int32)
            out = acc.astype(jnp.float32) * (p["as"] *
                                             p["ws"].reshape(1, -1))
            return out.reshape(lead + (out.shape[-1],))
        return jnp.matmul(x, p["w"].T)

    def _head_logits(self, params, x):
        import jax.numpy as jnp
        if "head" in params:
            return self._proj(x, params["head"])
        return jnp.matmul(x, params["embed"].T)

    def _build_prefill(self, bucket):
        """Prefill graph for one prompt padded to ``bucket`` tokens:
        causal forward (the same flash path the full forward runs),
        K/V written into the sequence's blocks, first token sampled from
        the last VALID position's logits."""
        import jax
        import jax.numpy as jnp
        from jax import lax
        from ..gluon.model_zoo.nlp.llama import _QPAD
        from ..ops.norm_rope import rms_norm as _rms, \
            rope_interleaved as _rot_interleaved
        from ..ops import quant_kv as _qkv
        from ..ops.flash_attention import flash_attention
        cfg = self.cfg
        h, kvh, d = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
        rep, eps, theta = h // kvh, cfg.rms_eps, cfg.rope_theta
        bs = self.block_size
        nb = bucket // bs
        L = bucket

        def body(params, kp, vp, ks, vs, toks, valid, bt, key):
            x = jnp.take(params["embed"], toks, axis=0)      # (1, L, hid)
            pos = jnp.arange(L)
            freqs = theta ** (-jnp.arange(0, d, 2) / d)
            ang = pos[:, None] * freqs[None, :]
            cos, sin = jnp.cos(ang), jnp.sin(ang)
            for li, lp in enumerate(params["layers"]):
                hh = _rms(x, lp["in_norm"], eps)
                q = self._proj(hh, lp["q"]).reshape(1, L, h, d) \
                    .transpose(0, 2, 1, 3)
                k = self._proj(hh, lp["k"]).reshape(1, L, kvh, d) \
                    .transpose(0, 2, 1, 3)
                v = self._proj(hh, lp["v"]).reshape(1, L, kvh, d) \
                    .transpose(0, 2, 1, 3)
                q = _rot_interleaved(q, cos, sin)
                k = _rot_interleaved(k, cos, sin)
                # unrepeated K/V into the pool blocks: (L, kvh, d) rows
                krows = k[0].transpose(1, 0, 2).reshape(nb, bs, kvh, d)
                vrows = v[0].transpose(1, 0, 2).reshape(nb, bs, kvh, d)
                if self._kv_fp8:
                    kq, ksc = _qkv.kv_quantize_fp8(krows)
                    vq, vsc = _qkv.kv_quantize_fp8(vrows)
                    kp = kp.at[li, bt].set(kq)
                    vp = vp.at[li, bt].set(vq)
                    ks = ks.at[li, bt].set(ksc)
                    vs = vs.at[li, bt].set(vsc)
                else:
                    kp = kp.at[li, bt].set(_qkv.kv_cast(krows, kp.dtype))
                    vp = vp.at[li, bt].set(_qkv.kv_cast(vrows, vp.dtype))
                # prefill's OWN attention reads the fresh f32 K/V —
                # quantization touches storage, never this math
                kr = jnp.repeat(k, rep, axis=1)
                vr = jnp.repeat(v, rep, axis=1)
                o = flash_attention(q, kr, vr, causal=True)
                o = o.transpose(0, 2, 1, 3).reshape(1, L, h * d)
                x = x + self._row_proj(o, lp["o"])
                y = _rms(x, lp["post_norm"], eps)
                x = x + self._row_proj(
                    jax.nn.silu(self._proj(y, lp["gate"])) *
                    self._proj(y, lp["up"]), lp["down"])
            x = _rms(x, params["norm"], eps)
            # last-valid-row logits through an M=_QPAD slice (an M=1
            # projection takes XLA's gemv path whose bits differ from
            # the full forward's gemm — see llama._QPAD)
            start = jnp.maximum(valid - _QPAD, 0)
            xs = lax.dynamic_slice_in_dim(x, start, _QPAD, axis=1)
            logits = self._head_logits(params, xs)[0]        # (_QPAD, V)
            last = jnp.take(logits, valid - 1 - start, axis=0)
            tok = self._sample(last[None, :], key)[0]
            kp, vp = self._shard_pools(kp, vp)
            if self._kv_fp8:
                ks, vs = self._shard_scales(ks, vs)
            return last, tok, kp, vp, ks, vs

        if self._kv_fp8:
            return body

        def run(params, kp, vp, toks, valid, bt, key):
            last, tok, kp, vp, _ks, _vs = body(
                params, kp, vp, None, None, toks, valid, bt, key)
            return last, tok, kp, vp

        return run

    def _decode_body(self, params, kp, vp, ks, vs, toks, pos, bts, blk,
                     nbl):
        """One decode step's layer stack, shared by the ``decode`` graph
        and every unrolled ``verify`` step (one source so speculative
        parity cannot drift): embed ``toks`` (B,), rotate at ``pos``,
        scatter K/V into ``blk``/offset, attend through the block
        table, and return (last-norm logits, kp, vp, ks, vs).

        The cache attention routes through
        ``ops.paged_attention.paged_decode_attention`` when
        ``paged_attn`` is set (whose XLA fallback is the inline gather
        below, verbatim) and stays inline otherwise — the kill switch
        compiles the exact PR 7 graph.

        Under fp8 KV storage (ISSUE 20) the scatter quantizes each
        row (amax scale into ``ks``/``vs``) and the gather dequantizes
        before the f32 attention math — the only drift source is the
        storage rounding of PAST tokens' K/V."""
        import jax
        import jax.numpy as jnp
        from ..gluon.model_zoo.nlp.llama import _cache_attention
        from ..ops.norm_rope import rms_norm as _rms, \
            rope_interleaved as _rot_interleaved
        from ..ops import quant_kv as _qkv
        cfg = self.cfg
        h, kvh, d = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
        eps, theta = cfg.rms_eps, cfg.rope_theta
        bs = self.block_size
        B = self.max_batch
        L = nbl * bs
        scale = 1.0 / math.sqrt(d)
        x = jnp.take(params["embed"], toks, axis=0)          # (B, hid)
        freqs = theta ** (-jnp.arange(0, d, 2) / d)
        ang = pos[:, None] * freqs[None, :]                  # (B, d/2)
        cos, sin = jnp.cos(ang), jnp.sin(ang)
        off = pos % bs
        valid = jnp.arange(L)[None, :] <= pos[:, None]       # (B, L)
        for li, lp in enumerate(params["layers"]):
            lp = self._gather_layer(lp)
            hh = _rms(x, lp["in_norm"], eps)
            q = self._proj(hh, lp["q"]).reshape(B, h, d)
            k = self._proj(hh, lp["k"]).reshape(B, kvh, d)
            v = self._proj(hh, lp["v"]).reshape(B, kvh, d)
            q = _rot_interleaved(q, cos[:, None, :], sin[:, None, :])
            k = _rot_interleaved(k, cos[:, None, :], sin[:, None, :])
            if self._kv_fp8:
                kq, ksc = _qkv.kv_quantize_fp8(k)
                vq, vsc = _qkv.kv_quantize_fp8(v)
                kp = kp.at[li, blk, off].set(kq)
                vp = vp.at[li, blk, off].set(vq)
                ks = ks.at[li, blk, off].set(ksc)
                vs = vs.at[li, blk, off].set(vsc)
            else:
                kp = kp.at[li, blk, off].set(_qkv.kv_cast(k, kp.dtype))
                vp = vp.at[li, blk, off].set(_qkv.kv_cast(v, vp.dtype))
            if self.paged_attn:
                from ..ops.paged_attention import paged_decode_attention
                kpl, vpl = self._gather_cache(kp[li], vp[li])
                if self._kv_fp8:
                    o = paged_decode_attention(q, kpl, vpl, bts, pos,
                                               scale, k_scale=ks[li],
                                               v_scale=vs[li])
                else:
                    o = paged_decode_attention(q, kpl, vpl, bts, pos,
                                               scale)
            else:
                ck = kp[li][bts].reshape(B, L, kvh, d)
                cv = vp[li][bts].reshape(B, L, kvh, d)
                if self._kv_fp8:
                    ck = _qkv.kv_dequantize(
                        ck, ks[li][bts].reshape(B, L))
                    cv = _qkv.kv_dequantize(
                        cv, vs[li][bts].reshape(B, L))
                elif self.kv_dtype is not None:
                    ck = _qkv.kv_dequantize(ck)
                    cv = _qkv.kv_dequantize(cv)
                ck = ck.transpose(0, 2, 1, 3)                # (B,kvh,L,d)
                cv = cv.transpose(0, 2, 1, 3)
                ck, cv = self._gather_cache(ck, cv)
                o = _cache_attention(q, ck, cv, valid, scale)
            x = x + self._row_proj(o, lp["o"])
            y = _rms(x, lp["post_norm"], eps)
            x = x + self._row_proj(
                jax.nn.silu(self._proj(y, lp["gate"])) *
                self._proj(y, lp["up"]), lp["down"])
        logits = self._head_logits(params, _rms(x, params["norm"], eps))
        kp, vp = self._shard_pools(kp, vp)
        if self._kv_fp8:
            ks, vs = self._shard_scales(ks, vs)
        return logits, kp, vp, ks, vs

    def _build_decode(self, nbl):
        """One-token decode for the fixed batch against ``nbl`` gathered
        blocks per sequence (context bucket = nbl * block_size)."""
        import jax.numpy as jnp
        bs = self.block_size

        def body(params, kp, vp, ks, vs, toks, pos, bts, active, key):
            blk = jnp.take_along_axis(
                bts, (pos // bs)[:, None], axis=1)[:, 0]     # (B,)
            blk = jnp.where(active, blk, 0)                  # null block
            logits, kp, vp, ks, vs = self._decode_body(
                params, kp, vp, ks, vs, toks, pos, bts, blk, nbl)
            return logits, self._sample(logits, key), kp, vp, ks, vs

        if self._kv_fp8:
            return body

        def run(params, kp, vp, toks, pos, bts, active, key):
            logits, tok, kp, vp, _ks, _vs = body(
                params, kp, vp, None, None, toks, pos, bts, active, key)
            return logits, tok, kp, vp

        return run

    def _build_verify(self, size):
        """Speculative verify graph: ``W`` decode steps unrolled in ONE
        dispatch (size = (W, nbl)).  Row i feeds its last committed
        token then its draft continuation at positions
        ``pos[i] .. pos[i] + counts[i] - 1``; step ``w`` scatters that
        token's K/V (visible to step ``w+1`` through the functional
        kp/vp threading) and argmaxes the next token.  Steps past a
        row's count write to the null block and their outputs are
        host-masked — a count-1 row is bitwise a plain decode row.

        Greedy-only by construction: acceptance is exact token
        equality against these argmaxes, so every accepted position's
        computation is identical to the plain decode path's and the
        committed stream is bitwise the non-speculative stream (the
        ISSUE 17 acceptance contract)."""
        import jax.numpy as jnp
        W, nbl = size
        bs = self.block_size

        def body(params, kp, vp, ks, vs, toks, pos, bts, counts, active,
                 key):
            outs = []
            for w in range(W):
                live = active & (w < counts)                 # (B,)
                pw = pos + w
                blk = jnp.take_along_axis(
                    bts, jnp.clip(pw // bs, 0, nbl - 1)[:, None],
                    axis=1)[:, 0]
                blk = jnp.where(live, blk, 0)                # null block
                logits, kp, vp, ks, vs = self._decode_body(
                    params, kp, vp, ks, vs, toks[:, w], pw, bts, blk,
                    nbl)
                outs.append(jnp.argmax(logits, axis=-1)
                            .astype(jnp.int32))
            return jnp.stack(outs, axis=1), kp, vp, ks, vs   # (B, W)

        if self._kv_fp8:
            return body

        def run(params, kp, vp, toks, pos, bts, counts, active, key):
            out, kp, vp, _ks, _vs = body(params, kp, vp, None, None,
                                         toks, pos, bts, counts, active,
                                         key)
            return out, kp, vp

        return run

    def _build_chunk_prefill(self, nbl):
        """Packed continuation prefill: up to ``max_batch`` rows, each a
        chunk of up to ``prefill_chunk`` prompt tokens starting at an
        arbitrary position, attending to ``nbl`` gathered blocks of that
        row's cache (offset-causal: key position <= query position).

        The attention is ``ops.flash_attention._scan_forward`` with the
        row index replaced by the ABSOLUTE position — same block
        decomposition, same einsum specs, same ``-1e30`` mask constant,
        same normalization order — so a chunk row's output (and the K/V
        it scatters) is bitwise the cold full-prefill's row for the
        same tokens (the prefix-cache parity gate,
        tests/test_serving_frontend.py)."""
        import jax
        import jax.numpy as jnp
        from jax import lax
        from ..ops.norm_rope import rms_norm as _rms, \
            rope_interleaved as _rot_interleaved
        from ..ops.flash_attention import _NEG_INF, _pick_block
        cfg = self.cfg
        h, kvh, d = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
        rep, eps, theta = h // kvh, cfg.rms_eps, cfg.rope_theta
        bs = self.block_size
        R, C = self.max_batch, self.prefill_chunk
        L = nbl * bs
        bk = _pick_block(L, 256) or L
        nk = L // bk
        scale = 1.0 / math.sqrt(d)

        def attend(q, kr, vr, qpos):
            # q (R*h, C, d); kr/vr (R*h, L, d); qpos (R*h, C) absolute
            kb = kr.reshape(R * h, nk, bk, d).transpose(1, 0, 2, 3)
            vb = vr.reshape(R * h, nk, bk, d).transpose(1, 0, 2, 3)

            def step(carry, blk):
                acc, m_i, l_i, j = carry
                kj, vj = blk
                s = jnp.einsum("bqd,bkd->bqk", q, kj,
                               preferred_element_type=jnp.float32) * scale
                kpos = j * bk + lax.broadcasted_iota(jnp.int32, (C, bk), 1)
                s = jnp.where(qpos[:, :, None] >= kpos[None], s, _NEG_INF)
                m_new = jnp.maximum(m_i, jnp.max(s, axis=-1,
                                                 keepdims=True))
                p = jnp.exp(s - m_new)
                alpha = jnp.exp(m_i - m_new)
                l_new = l_i * alpha + jnp.sum(p, axis=-1, keepdims=True)
                acc = acc * alpha + jnp.einsum(
                    "bqk,bkd->bqd", p.astype(vr.dtype), vj,
                    preferred_element_type=jnp.float32)
                return (acc, m_new, l_new, j + 1), None

            init = (jnp.zeros((R * h, C, d), jnp.float32),
                    jnp.full((R * h, C, 1), _NEG_INF, jnp.float32),
                    jnp.zeros((R * h, C, 1), jnp.float32),
                    jnp.int32(0))
            (acc, m_i, l_i, _), _ = lax.scan(step, init, (kb, vb))
            return (acc / jnp.maximum(l_i, 1e-30)).astype(q.dtype)

        def body(params, kp, vp, ks, vs, toks, starts, valids, bts,
                 active, key):
            from ..ops import quant_kv as _qkv
            x = jnp.take(params["embed"], toks, axis=0)      # (R, C, hid)
            cidx = jnp.arange(C)
            abs_pos = starts[:, None] + cidx[None, :]        # (R, C)
            freqs = theta ** (-jnp.arange(0, d, 2) / d)
            ang = abs_pos[..., None] * freqs
            cos, sin = jnp.cos(ang), jnp.sin(ang)            # (R, C, d/2)
            write = active[:, None] & (cidx[None, :] < valids[:, None])
            blk = jnp.take_along_axis(
                bts, jnp.clip(abs_pos // bs, 0, nbl - 1), axis=1)
            blk = jnp.where(write, blk, 0)                   # null block
            off = abs_pos % bs
            qpos = jnp.repeat(abs_pos, h, axis=0)            # (R*h, C)
            for li, lp in enumerate(params["layers"]):
                hh = _rms(x, lp["in_norm"], eps)
                q = self._proj(hh, lp["q"]).reshape(R, C, h, d) \
                    .transpose(0, 2, 1, 3)
                k = self._proj(hh, lp["k"]).reshape(R, C, kvh, d) \
                    .transpose(0, 2, 1, 3)
                v = self._proj(hh, lp["v"]).reshape(R, C, kvh, d)
                q = _rot_interleaved(q, cos[:, None], sin[:, None])
                k = _rot_interleaved(k, cos[:, None], sin[:, None])
                krows = k.transpose(0, 2, 1, 3)              # (R,C,kvh,d)
                if self._kv_fp8:
                    kq, ksc = _qkv.kv_quantize_fp8(krows)
                    vq, vsc = _qkv.kv_quantize_fp8(v)
                    kp = kp.at[li, blk, off].set(kq)
                    vp = vp.at[li, blk, off].set(vq)
                    ks = ks.at[li, blk, off].set(ksc)
                    vs = vs.at[li, blk, off].set(vsc)
                else:
                    kp = kp.at[li, blk, off].set(
                        _qkv.kv_cast(krows, kp.dtype))
                    vp = vp.at[li, blk, off].set(
                        _qkv.kv_cast(v, vp.dtype))
                ck = kp[li][bts].reshape(R, L, kvh, d)
                cv = vp[li][bts].reshape(R, L, kvh, d)
                if self._kv_fp8:
                    ck = _qkv.kv_dequantize(
                        ck, ks[li][bts].reshape(R, L))
                    cv = _qkv.kv_dequantize(
                        cv, vs[li][bts].reshape(R, L))
                elif self.kv_dtype is not None:
                    ck = _qkv.kv_dequantize(ck)
                    cv = _qkv.kv_dequantize(cv)
                ck = ck.transpose(0, 2, 1, 3)                # (R,kvh,L,d)
                cv = cv.transpose(0, 2, 1, 3)
                kr = jnp.repeat(ck, rep, axis=1).reshape(R * h, L, d)
                vr = jnp.repeat(cv, rep, axis=1).reshape(R * h, L, d)
                o = attend(q.reshape(R * h, C, d), kr, vr, qpos)
                o = o.reshape(R, h, C, d).transpose(0, 2, 1, 3) \
                    .reshape(R, C, h * d)
                x = x + self._row_proj(o, lp["o"])
                y = _rms(x, lp["post_norm"], eps)
                x = x + self._row_proj(
                    jax.nn.silu(self._proj(y, lp["gate"])) *
                    self._proj(y, lp["up"]), lp["down"])
            x = _rms(x, params["norm"], eps)
            logits = self._head_logits(params, x)            # (R, C, V)
            last = jnp.take_along_axis(
                logits, jnp.clip(valids - 1, 0, C - 1)[:, None, None],
                axis=1)[:, 0]                                # (R, V)
            kp, vp = self._shard_pools(kp, vp)
            if self._kv_fp8:
                ks, vs = self._shard_scales(ks, vs)
            return last, self._sample(last, key), kp, vp, ks, vs

        if self._kv_fp8:
            return body

        def run(params, kp, vp, toks, starts, valids, bts, active, key):
            last, nxt, kp, vp, _ks, _vs = body(
                params, kp, vp, None, None, toks, starts, valids, bts,
                active, key)
            return last, nxt, kp, vp

        return run

    def _build_cow(self, _size):
        """Copy-on-write block fork: duplicate one physical block's K/V
        (all layers) into a freshly allocated block, pools donated.
        Under fp8 KV the per-row amax scales ride along — a forked
        block must dequantize identically to its source."""
        if self._kv_fp8:
            def run_fp8(kp, vp, ks, vs, src, dst):
                kp, vp = self._shard_pools(
                    kp.at[:, dst].set(kp[:, src]),
                    vp.at[:, dst].set(vp[:, src]))
                ks, vs = self._shard_scales(
                    ks.at[:, dst].set(ks[:, src]),
                    vs.at[:, dst].set(vs[:, src]))
                return kp, vp, ks, vs
            return run_fp8

        def run(kp, vp, src, dst):
            return self._shard_pools(kp.at[:, dst].set(kp[:, src]),
                                     vp.at[:, dst].set(vp[:, src]))
        return run

    def _sample(self, logits, key):
        """In-graph next-token sampling: greedy at temperature 0, else
        (top-k) categorical — logits never leave the device per token."""
        import jax
        import jax.numpy as jnp
        if self.temperature == 0.0:
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)
        scaled = logits.astype(jnp.float32) / self.temperature
        if self.top_k > 0:
            vals, idx = jax.lax.top_k(scaled, self.top_k)
            pick = jax.random.categorical(key, vals, axis=-1)
            return jnp.take_along_axis(
                idx, pick[:, None], axis=1)[:, 0].astype(jnp.int32)
        return jax.random.categorical(key, scaled,
                                      axis=-1).astype(jnp.int32)

    # -- compile cache (the retrace-detector discipline) -----------------

    def _sig(self, kind, size):
        # paged_attn is part of the signature: the routing changes the
        # compiled graph body, so a SHARED cache (Router fleets) must
        # never hand a paged executable to an inline engine or back.
        # The mesh spec rides too (ISSUE 18): a tp-sharded executable
        # bakes its input shardings in, so a shared cache must never
        # serve it to an engine on a different submesh.
        # kv_dtype rides too (ISSUE 20): the fp8 graphs take the scale
        # planes as extra donated args, so a shared cache must never
        # hand an fp8 executable to a full-precision engine or back.
        return (kind, size, self.cache.num_blocks, self.max_batch,
                self.block_size, self.paged_attn,
                self.mesh_config.describe(), self.kv_dtype)

    def _get(self, kind, size, args):
        """Compile-cache lookup keyed by (kind, shape-signature); every
        miss is one AOT compile (``jit(...).lower(args).compile()``) and
        is COUNTED — serving traffic after warmup() must never miss.
        The cached object is a fixed executable, so an unexpected
        shape/dtype drift raises loudly instead of retracing silently
        (the PR 1 retrace-detector discipline, enforced not observed).
        The signature carries the pool geometry so a SHARED cache
        (Router fleets) only ever serves executables whose donated pool
        shapes match this engine's."""
        sig = self._sig(kind, size)
        fn = self._compiled.get(sig)
        if fn is None:
            import jax
            tc0 = _trace.clock() if _trace.enabled() else None
            build = {"prefill": self._build_prefill,
                     "decode": self._build_decode,
                     "chunk": self._build_chunk_prefill,
                     "verify": self._build_verify,
                     "cow": self._build_cow}[kind](size)
            if self._kv_fp8:
                # scale planes are donated alongside the pools: fp8 cow
                # is run(kp, vp, ks, vs, src, dst); the other families
                # take (params, kp, vp, ks, vs, ...)
                donate = (0, 1, 2, 3) if kind == "cow" else (1, 2, 3, 4)
            else:
                donate = (0, 1) if kind == "cow" else (1, 2)
            fn = jax.jit(build, donate_argnums=donate) \
                .lower(*args).compile()
            self._compiled[sig] = fn
            self.stats["compiles"] += 1
            _telem.inc("serving.compiles")
            # verify sizes are (width, n_blocks) tuples; keep ints for
            # the scalar families (existing telemetry schema)
            sz = int(size) if isinstance(size, int) else str(size)
            if tc0 is not None:
                # compiles on the request timeline: a warmup-miss that
                # stalls traffic is visible exactly where it hurt
                _trace.record("engine.compile", tc0, _trace.clock(),
                              kind=kind, size=sz)
            if self._warmed:
                # the tier-1 zero-retrace assertion reads the engine's
                # own counter; the registry twin is what a live scrape
                # sees (one source of truth for the loadgen, ISSUE 9)
                self.stats["compiles_after_warmup"] += 1
                _telem.inc("serving.compiles_after_warmup")
                _telem.event("serving.compile_after_warmup",
                             kind=kind, size=sz)
        return fn

    def _verify_widths(self):
        """Compiled verify widths: the power-of-two buckets covering up
        to ``spec_k + 1`` fed tokens (last committed + drafts), floor 2
        — a 1-token boundary uses the plain decode graph instead."""
        top = 2
        while top < self.spec_k + 1:
            top *= 2
        out, w = [], 2
        while w <= top:
            out.append(w)
            w *= 2
        return out

    def warmup(self):
        """AOT-compile every (prefill, decode[, chunk, cow]) bucket
        graph by running each once against the real pools (compile +
        execute warms the jit cache; the pools round-trip through the
        donated call).  Graphs already present in a SHARED compile
        cache (Router replicas) are skipped outright — the fleet
        compiles each signature once."""
        import jax
        dummy_key = jax.random.key(0)
        for bucket in self.buckets:
            nb = bucket // self.block_size
            if self._sig("prefill", bucket) in self._compiled and \
                    self._sig("decode", nb) in self._compiled:
                continue
            ok = self.cache.alloc("__warmup__", bucket)
            if not ok:
                raise MXNetError("warmup: KV pool too small for bucket "
                                 f"{bucket}; raise num_blocks")
            bt = _np.asarray(self.cache.table("__warmup__"), _np.int32)
            toks = _np.zeros((1, bucket), _np.int32)
            args = (self.params,) + self.cache.pool_args() + \
                (toks, _np.int32(1), bt, dummy_key)
            out = self._get("prefill", bucket, args)(*args)
            self.cache.update_pools(
                *out[2:], site="InferenceEngine.warmup(prefill)")
            bts = self.cache.table_array(
                ["__warmup__"] + [None] * (self.max_batch - 1), nb)
            args = (self.params,) + self.cache.pool_args() + \
                (_np.zeros((self.max_batch,), _np.int32),
                 _np.zeros((self.max_batch,), _np.int32), bts,
                 _np.zeros((self.max_batch,), bool), dummy_key)
            out = self._get("decode", nb, args)(*args)
            self.cache.update_pools(
                *out[2:], site="InferenceEngine.warmup(decode)")
            self.cache.free("__warmup__")
        if self.prefill_chunk:
            # the packed-chunk family: one graph per context bucket,
            # warmed with every row inactive (all writes land in the
            # null block, so no pool allocation is needed)
            R, C = self.max_batch, self.prefill_chunk
            for bucket in self.buckets:
                nb = bucket // self.block_size
                if self._sig("chunk", nb) in self._compiled:
                    continue
                args = (self.params,) + self.cache.pool_args() + \
                    (_np.zeros((R, C), _np.int32),
                     _np.zeros((R,), _np.int32),
                     _np.zeros((R,), _np.int32),
                     _np.zeros((R, nb), _np.int32),
                     _np.zeros((R,), bool), dummy_key)
                out = self._get("chunk", nb, args)(*args)
                self.cache.update_pools(
                    *out[2:], site="InferenceEngine.warmup(chunk)")
        if self.spec_decode:
            # the speculative verify family: one graph per (width,
            # context bucket), warmed all-inactive like the chunk family
            # (dead rows write the null block — no pool allocation)
            B = self.max_batch
            for W in self._verify_widths():
                for bucket in self.buckets:
                    nb = bucket // self.block_size
                    if self._sig("verify", (W, nb)) in self._compiled:
                        continue
                    args = (self.params,) + self.cache.pool_args() + \
                        (_np.zeros((B, W), _np.int32),
                         _np.zeros((B,), _np.int32),
                         _np.zeros((B, nb), _np.int32),
                         _np.zeros((B,), _np.int32),
                         _np.zeros((B,), bool), dummy_key)
                    out = self._get("verify", (W, nb), args)(*args)
                    self.cache.update_pools(
                        *out[1:], site="InferenceEngine.warmup(verify)")
        if self.prefill_chunk or self.prefix_cache is not None:
            if self._sig("cow", 0) not in self._compiled:
                # the copy-on-write block copy (src=dst=0 copies the
                # null block onto itself — garbage by design)
                args = self.cache.pool_args() + \
                    (_np.int32(0), _np.int32(0))
                out = self._get("cow", 0, args)(*args)
                self.cache.update_pools(
                    *out, site="InferenceEngine.warmup(cow)")
        self._warmed = True
        return self

    # -- serving calls ---------------------------------------------------

    def prefill(self, slot, tokens):
        """Prefill ``tokens`` (1D int sequence) into ``slot``: allocates
        blocks, runs the bucketed prefill graph, samples the first
        generated token.  Returns ``(first_token, last_logits)`` or None
        when the prompt exceeds max_context or the pool is exhausted
        (request stays queued)."""
        import jax
        toks = _np.asarray(tokens, _np.int32).reshape(-1)
        t = toks.shape[0]
        if t == 0:
            raise MXNetError("prefill needs at least one token")
        bucket = next_bucket(t, self.buckets)
        if bucket is None:
            return None
        if not self.cache.alloc(slot, bucket):
            return None
        padded = _np.zeros((1, bucket), _np.int32)
        padded[0, :t] = toks
        bt = _np.asarray(self.cache.table(slot), _np.int32)
        key = jax.random.fold_in(self._base_key,
                                 (1 << 30) + self.stats["prefill_calls"])
        args = (self.params,) + self.cache.pool_args() + \
            (padded, _np.int32(t), bt, key)
        t0 = _telem.clock() if _telem.enabled() else None
        out = self._get("prefill", bucket, args)(*args)
        last, tok = out[0], out[1]
        self.cache.update_pools(*out[2:], site="InferenceEngine.prefill")
        self.cache.trim(slot, t)
        self.cache.set_len(slot, t)
        self.stats["prefill_calls"] += 1
        self.stats["prompt_tokens_computed"] += t
        if t0 is not None:
            _telem.inc("serving.prefill_calls")
            _telem.observe("serving.prefill_ms",
                           (_telem.clock() - t0) * 1e3)
            self._publish_cache_gauges()
        return int(tok), last

    def attach_prefix(self, slot, tokens):
        """Prefix-cache admission: adopt the longest cached block chain
        that prefixes ``tokens`` into ``slot`` (refcounts bumped, zero
        compute) and return the number of cached positions (0 = miss or
        no prefix cache; the caller prefills from there)."""
        if self.prefix_cache is None:
            return 0
        return self.prefix_cache.attach(slot, tokens)

    def insert_prefix(self, slot, tokens):
        """Register ``slot``'s freshly prefilled prompt in the prefix
        cache so later requests sharing the prefix skip its compute."""
        if self.prefix_cache is not None:
            self.prefix_cache.insert(slot, tokens)

    def pin_prefix(self, tokens):
        """Prefill ``tokens`` ONCE into a temporary slot and pin the
        chain — including the partial tail block — in the prefix cache.
        The deliberate system-prompt seam: every later request starting
        with ``tokens`` adopts the blocks (CoW on its first write past
        them) instead of recomputing.  Returns False when the pool
        cannot hold the prefix right now."""
        if self.prefix_cache is None:
            raise MXNetError("pin_prefix needs prefix_cache=True")
        # id(self) namespaces the pin against OTHER engines on a shared
        # pool (disaggregated fleet): two replicas pinning their first
        # prefix must not collide on the same slot key
        slot = ("__prefix_pin__", id(self), self.stats["prefill_calls"])
        if self.prefill(slot, tokens) is None:
            return False
        self.prefix_cache.insert(slot, tokens)
        self.release(slot)
        return True

    def chunk_prefill(self, entries):
        """One PACKED continuation-prefill dispatch (the ISSUE 12
        chunked/batched prefill): ``entries`` is a list of
        ``(slot, tokens, start)`` rows — ``tokens`` (<= prefill_chunk of
        them) are the prompt positions ``[start, start+n)`` of ``slot``,
        whose table already caches everything before ``start``.

        Allocates/CoW-forks the written blocks, runs the compiled chunk
        graph once for ALL rows, and returns ``(next_tokens, logits)``
        aligned with ``entries`` (row meaningful only for rows whose
        chunk ends the prompt).  Returns None when the pool cannot
        cover the chunk (callers may evict prefix chains and retry)."""
        import jax
        if not self.prefill_chunk:
            raise MXNetError("chunk_prefill needs prefill_chunk > 0 "
                             "(MXTPU_PREFILL_CHUNK)")
        n = len(entries)
        if not 1 <= n <= self.max_batch:
            raise MXNetError(f"chunk_prefill: {n} rows vs max_batch "
                             f"{self.max_batch}")
        C = self.prefill_chunk
        end_max = 0
        for slot, toks, start in entries:
            t = len(toks)
            if not 1 <= t <= C:
                raise MXNetError(f"chunk of {t} tokens vs chunk bucket "
                                 f"{C}")
            if not self.cache.ensure(slot, start + t - 1):
                return None
            copies = self.cache.prepare_write(slot, start, start + t)
            if copies is None:
                return None
            self._apply_cow(copies)
            end_max = max(end_max, start + t)
        bucket = next_bucket(end_max, self.buckets)
        if bucket is None:
            raise MXNetError(f"chunk end {end_max} exceeds max_context "
                             f"{self.max_context}")
        nbl = bucket // self.block_size
        R = self.max_batch
        toks = _np.zeros((R, C), _np.int32)
        starts = _np.zeros((R,), _np.int32)
        valids = _np.zeros((R,), _np.int32)
        active = _np.zeros((R,), bool)
        slots = [None] * R
        for i, (slot, chunk, start) in enumerate(entries):
            toks[i, :len(chunk)] = _np.asarray(chunk, _np.int32)
            starts[i], valids[i], active[i] = start, len(chunk), True
            slots[i] = slot
        bts = self.cache.table_array(slots, nbl)
        key = jax.random.fold_in(self._base_key,
                                 (1 << 29) +
                                 self.stats["chunk_prefill_calls"])
        args = (self.params,) + self.cache.pool_args() + \
            (toks, starts, valids, bts, active, key)
        t0 = _telem.clock() if _telem.enabled() else None
        out = self._get("chunk", nbl, args)(*args)
        last, nxt = out[0], out[1]
        self.cache.update_pools(*out[2:],
                                site="InferenceEngine.chunk_prefill")
        for slot, chunk, start in entries:
            self.cache.set_len(slot, start + len(chunk))
        self.stats["chunk_prefill_calls"] += 1
        self.stats["prompt_tokens_computed"] += \
            int(sum(len(c) for _s, c, _p in entries))
        if t0 is not None:
            _telem.inc("serving.chunk_prefill_calls")
            _telem.observe("serving.chunk_prefill_ms",
                           (_telem.clock() - t0) * 1e3)
            self._publish_cache_gauges()
        return _np.asarray(nxt)[:n], _np.asarray(last)[:n]

    def _apply_cow(self, copies):
        """Run the device half of each (src -> dst) copy-on-write fork
        the cache planned: the new block must carry the shared block's
        bits before the caller's write lands."""
        for src, dst in copies:
            args = self.cache.pool_args() + \
                (_np.int32(src), _np.int32(dst))
            out = self._get("cow", 0, args)(*args)
            self.cache.update_pools(*out,
                                    site="InferenceEngine._apply_cow")

    def _publish_cache_gauges(self):
        _telem.set_gauge("serving.kv_block_utilization",
                         round(self.cache.utilization(), 4))
        _telem.set_gauge("serving.kv_blocks_in_use",
                         self.cache.blocks_in_use)
        # memory honesty (ISSUE 15): exact bytes the live block-table
        # entries pin, so an OOM post-mortem names the KV pool by size
        _telem.set_gauge("serving.kv_bytes_in_use",
                         self.cache.blocks_in_use
                         * self.cache.block_nbytes)
        if self.prefix_cache is not None:
            hr = self.prefix_cache.hit_rate()
            if hr is not None:
                _telem.set_gauge("serving.prefix_hit_rate",
                                 round(hr, 4))

    def reserve(self, slot, pos, n=1):
        """Grow ``slot``'s block table to cover positions
        ``[pos, pos + n)`` before a decode/verify step,
        copy-on-write-forking written blocks a prefix chain still
        shares.  ``n > 1`` is the speculative write-ahead: the verify
        graph scatters the whole draft window before acceptance is
        known (rejected positions stay garbage until ``trim``).  Under
        pool pressure, LRU prefix chains are evicted first (only chains
        — never a block a live sequence holds); False when the pool is
        exhausted even then."""
        pc = self.prefix_cache
        last = pos + n - 1
        if not self.cache.ensure(slot, last):
            need = self.cache.blocks_for(last + 1) - \
                len(self.cache.table(slot))
            if pc is None or not pc.evict(blocks_needed=need):
                return False
            if not self.cache.ensure(slot, last):
                return False
        copies = self.cache.prepare_write(slot, pos, pos + n)
        if copies is None:
            if pc is None or not pc.evict(blocks_needed=1):
                return False
            copies = self.cache.prepare_write(slot, pos, pos + n)
            if copies is None:
                return False
        self._apply_cow(copies)
        return True

    def decode(self, entries):
        """One decode step for the joined batch.

        entries: list of (slot, token, position) for the ACTIVE rows
        (position = where this token goes, i.e. current sequence
        length).  Pads to the fixed batch, picks the context bucket from
        the max position, gathers block tables, runs the compiled step.
        Returns (next_tokens (n_active,) np.int32, logits rows).
        """
        import jax
        if not entries:
            raise MXNetError("decode: empty batch")
        n = len(entries)
        if n > self.max_batch:
            raise MXNetError(f"decode batch {n} > max_batch")
        max_pos = max(p for _, _, p in entries)
        bucket = next_bucket(max_pos + 1, self.buckets)
        if bucket is None:
            raise MXNetError(f"position {max_pos} exceeds max_context "
                             f"{self.max_context}")
        nbl = bucket // self.block_size
        slots = [s for s, _, _ in entries] + \
            [None] * (self.max_batch - n)
        toks = _np.zeros((self.max_batch,), _np.int32)
        pos = _np.zeros((self.max_batch,), _np.int32)
        active = _np.zeros((self.max_batch,), bool)
        for i, (slot, tok, p) in enumerate(entries):
            toks[i], pos[i], active[i] = tok, p, True
            self.cache.set_len(slot, p + 1)
        bts = self.cache.table_array(slots, nbl)
        key = jax.random.fold_in(self._base_key,
                                 self.stats["decode_calls"])
        args = (self.params,) + self.cache.pool_args() + \
            (toks, pos, bts, active, key)
        t0 = _telem.clock() if _telem.enabled() else None
        out = self._get("decode", nbl, args)(*args)
        logits, nxt = out[0], out[1]
        self.cache.update_pools(*out[2:], site="InferenceEngine.decode")
        self.stats["decode_calls"] += 1
        if t0 is not None:
            _telem.inc("serving.decode_calls")
            _telem.observe("serving.decode_ms",
                           (_telem.clock() - t0) * 1e3)
            self._publish_cache_gauges()
        nxt = _np.asarray(nxt)[:n]
        return nxt, _np.asarray(logits)[:n]

    def verify(self, entries):
        """One speculative verify dispatch (ISSUE 17).

        entries: list of ``(slot, tokens, position)`` — ``tokens`` is
        the row's last committed token followed by its draft
        continuation (1 <= len <= spec_k + 1), fed at positions
        ``position .. position + len - 1``.  The caller must have
        :meth:`reserve`\\ d that whole range.  Returns ``out``
        (n_active, W) np.int32 where ``out[i, j]`` is the greedy token
        after absorbing ``tokens[i][:j+1]`` — the caller commits the
        prefix of drafts that match and trims the write-ahead past the
        committed length (see ContinuousBatcher._decode_spec)."""
        import jax
        if not entries:
            raise MXNetError("verify: empty batch")
        if self.temperature != 0.0:
            raise NotSupportedError(
                "verify is greedy-only; sampled decoding keeps the "
                "plain decode path")
        n = len(entries)
        if n > self.max_batch:
            raise MXNetError(f"verify batch {n} > max_batch")
        wmax = max(len(t) for _, t, _ in entries)
        if wmax < 1:
            raise MXNetError("verify: empty token row")
        if wmax > self.spec_k + 1:
            raise MXNetError(f"verify row of {wmax} tokens vs spec_k "
                             f"{self.spec_k} (+1 committed)")
        W = next_bucket(max(wmax, 2), self._verify_widths())
        end_max = max(p + len(t) for _, t, p in entries)
        bucket = next_bucket(end_max, self.buckets)
        if bucket is None:
            raise MXNetError(f"verify end {end_max} exceeds "
                             f"max_context {self.max_context}")
        nbl = bucket // self.block_size
        slots = [s for s, _, _ in entries] + \
            [None] * (self.max_batch - n)
        toks = _np.zeros((self.max_batch, W), _np.int32)
        pos = _np.zeros((self.max_batch,), _np.int32)
        counts = _np.zeros((self.max_batch,), _np.int32)
        active = _np.zeros((self.max_batch,), bool)
        for i, (slot, tk, p) in enumerate(entries):
            tk = _np.asarray(tk, _np.int32).reshape(-1)
            toks[i, :tk.shape[0]] = tk
            pos[i], counts[i], active[i] = p, tk.shape[0], True
            # write-ahead length; the scheduler trims back to the
            # committed length after acceptance
            self.cache.set_len(slot, p + tk.shape[0])
        bts = self.cache.table_array(slots, nbl)
        key = jax.random.fold_in(self._base_key,
                                 (1 << 28) + self.stats["verify_calls"])
        args = (self.params,) + self.cache.pool_args() + \
            (toks, pos, bts, counts, active, key)
        t0 = _telem.clock() if _telem.enabled() else None
        res = self._get("verify", (W, nbl), args)(*args)
        out = res[0]
        self.cache.update_pools(*res[1:], site="InferenceEngine.verify")
        self.stats["verify_calls"] += 1
        self.stats["draft_tokens_scored"] += \
            int(sum(len(t) - 1 for _, t, _ in entries))
        if t0 is not None:
            _telem.inc("serving.verify_calls")
            _telem.observe("serving.verify_ms",
                           (_telem.clock() - t0) * 1e3)
            self._publish_cache_gauges()
        return _np.asarray(out)[:n]

    def release(self, slot):
        """Finished sequence: drop its hold on its blocks (a block a
        prefix chain still references survives in the pool)."""
        self.cache.free(slot)
        if _telem.enabled():
            self._publish_cache_gauges()
