"""AMP op lists (reference: python/mxnet/contrib/amp/lists/symbol.py).

Three classes, MXNet's scheme:
- TARGET_DTYPE_OPS: run in the low-precision target dtype (bf16 on TPU —
  these are the MXU ops where reduced precision buys throughput).
- FP32_OPS: numerically sensitive; inputs are cast up to float32.
- WIDEST_TYPE_CASTS: multi-input ops whose inputs are cast to the widest
  dtype among them (e.g. elementwise add of bf16 + fp32).
Everything unlisted runs in whatever dtype arrives.
"""

# MXU-bound ops: matmuls / convs / rnn — the fp16 whitelist of the reference
TARGET_DTYPE_OPS = [
    "FullyConnected", "Convolution", "Deconvolution", "dot", "batch_dot",
    "linalg_gemm2", "RNN",
    # latent attention (RoPE + the flash kernel) and the routed experts'
    # grouped products; the shared expert and every projection are Dense,
    # i.e. FullyConnected
    "mla_attention", "moe_experts",
    # sparse grouped-query attention: the attention and the indexer's
    # q . k products; its index weights, the sum over index heads, the
    # threshold and both comparisons stay float32 inside the op
    "sparse_gq_attention",
    # Kimi Delta Attention's scan: q, k (normalised in float32 inside the op)
    # and v into the products; the log-decay, beta, every decay factor, the
    # triangular inverse and the carried state stay float32 inside the op
    "kda_attention",
    # block-diffusion attention: the flash kernels under block rules; the
    # rotary angles and each query's own noisy block stay float32 inside
    "block_diffusion_attention",
]

# the reference's fp32 blacklist: softmax family, norms, losses, exp/log/pow
FP32_OPS = [
    "softmax", "log_softmax", "softmin", "SoftmaxActivation", "SoftmaxOutput",
    "softmax_cross_entropy", "BatchNorm", "LayerNorm", "InstanceNorm",
    "L2Normalization", "norm", "exp", "log", "log2", "log10", "expm1",
    "log1p", "erf", "gamma", "gammaln", "smooth_l1", "mean", "sum", "nansum",
    "prod", "nanprod", "cumsum",
    # RMSNorm (the layer's and the latent's) and the router's sigmoid scores,
    # whose top-k must not move with bf16 rounding
    "rms_norm", "moe_router",
    # Kimi Delta Attention's decay (softplus, exp) and step size (sigmoid)
    "kda_gate",
]

# arguments that keep the dtype they arrive in although their op is listed
# above: the router's expert ids and float32 combine weights on their way into
# the bfloat16 expert products (the combine sums in float32)
KEEP_DTYPE_ARGS = {"moe_experts": ("experts", "weights"),
                   "sparse_gq_attention": ("x_index", "w_index",
                                           "positions"),
                   "kda_attention": ("g", "beta")}

WIDEST_TYPE_CASTS = [
    "add_n", "concat", "stack", "where", "broadcast_add", "broadcast_sub",
    "broadcast_mul", "broadcast_div",
]
