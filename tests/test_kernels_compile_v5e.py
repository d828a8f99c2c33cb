"""The Pallas kernels of the benchmark's cells, compiled at the cells' own shapes
for a *described* v5e (no chip attached): Mosaic runs inside that compile, so
a block it will not tile or more VMEM than a kernel may use fails here, at no
chip time.  Nothing runs; nothing here is a time.

The topology is described inside a fixture, never at import (one process at
a time may load the TPU's library: on-chip-measurement guide, section 2), and
this is the one file that does it."""
import os
import re
from unittest import mock

import pytest

import jax
import jax.numpy as jnp


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    env = {"TPU_LOG_DIR": "disabled", "TPU_ACCELERATOR_TYPE": "v5litepod-4",
           "TPU_WORKER_HOSTNAMES": "localhost", "TPU_SKIP_MDS_QUERY": "1"}
    with mock.patch.dict(os.environ, {k: v for k, v in env.items()
                                      if k not in os.environ}):
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 - whatever stops it, skip
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, *shapes):
    """Compile for the described chip with the persistent cache off (an
    entry written by such a compile cannot be read back without a chip) and
    the kernels' backend probe answering as it would there."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        with mock.patch.object(jax, "default_backend", lambda: "tpu"):
            return jax.jit(fn).lower(*shapes).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


@pytest.mark.parametrize("b,h,seq,d,dv,causal", [
    pytest.param(2, 32, 4096, 192, 128, True, id="mla-s4096"),
    pytest.param(32, 12, 512, 64, 64, False, id="bert-s512"),
    pytest.param(128, 12, 128, 64, 64, False, id="bert-s128"),
])
def test_flash_forward_and_backward_at_the_mla_shape(one_chip, b, h, seq, d,
                                                     dv, causal):
    """The forward kernel and the backward kernel at the three cells' own
    shapes (the latent-attention one streams 512-blocks, BERT's are one
    pass): one Mosaic call each, by name, and no scan left."""
    from mxnet_tpu.ops import flash_attention

    def shape(d):
        return jax.ShapeDtypeStruct((b, h, seq, d), jnp.bfloat16,
                                    sharding=one_chip)

    def grads(q, k, v):
        return jax.grad(lambda *a: flash_attention(*a, causal=causal).astype(
            jnp.float32).sum(), argnums=(0, 1, 2))(q, k, v)
    text = _compile(grads, shape(d), shape(d), shape(dv))
    calls = [ln for ln in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in ln]
    # the pallas_call's name= is a component of the call's op_name
    assert sorted(re.search(r'op_name="[^"]*(mxtpu_flash_\w+)', ln).group(1)
                  for ln in calls) == ["mxtpu_flash_bwd", "mxtpu_flash_fwd"]
    assert " while(" not in text


@pytest.mark.parametrize("k,n", [(2048, 768), (768, 2048)])
def test_grouped_product_and_both_backward_kernels_at_the_expert_widths(
        one_chip, k, n):
    from mxnet_tpu.ops.grouped_matmul import grouped_matmul

    def shaped(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def all_three(lhs, rhs, sizes):
        out, vjp = jax.vjp(lambda a, b: grouped_matmul(a, b, sizes), lhs,
                           rhs)
        return (out,) + vjp(out)
    text = _compile(all_three, shaped((49152, k)), shaped((16, k, n)),
                    shaped((16,), jnp.int32))
    assert text.count("tpu_custom_call") == 3
    for name in ("mxtpu_gmm", "mxtpu_gmm_dlhs", "mxtpu_gmm_drhs"):
        assert name in text


@pytest.mark.parametrize("part", ["index_select", "attention",
                                  "attention-repeated", "align_loss"])
def test_sparse_attention_kernels_at_the_keye_shape(one_chip, part):
    """The keye cell's four kernels at its own shapes (one sequence of
    16384, 32 / 4 heads of 128, 16 index heads of 64, top-2048): the
    index / select kernel with its (L, 128) scratch of keys, the masked
    streaming flash forward and backward with K / V at their 4 heads as the
    op hands them on (the forward's programs of 8 query heads, 256-blocks,
    8 KV blocks a grid step; the backward's of the same 8 heads at
    512-blocks, Q blocks outer, the kv head's float32 dK / dV rows, 16 MiB,
    in VMEM: Mosaic's limit is raised) and repeated to the 32 (a group of
    1: the parent's programs, the backward's holding a row's 8 MiB float32
    dQ), and the alignment loss's value kernel and
    gradient kernel (the latter with its 4 MiB scratch and the resident
    ``dki`` row): one Mosaic call each, by name, and no scan left."""
    from mxnet_tpu import telemetry
    from mxnet_tpu.ops import sparse_attention as sa
    from mxnet_tpu.ops.flash_attention import masked_flash
    b, h, hkv, seq, d, hi, di, topk = 1, 32, 4, 16384, 128, 16, 64, 2048

    def shape(dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)
    mask, f32 = shape((b, seq, seq), jnp.int8), jnp.float32
    indexer = (shape((b, hi, seq, di)), shape((b, seq, di)),
               shape((b, seq, hi), f32))
    if part == "index_select":
        text = _compile(lambda qi, ki, w: sa._index_select(
            qi, ki, w, topk, 1 / 32), *indexer)
        names = ["mxtpu_dsa_index_select"]
    elif part.startswith("attention"):
        def both(q, k, v, mask):
            return jax.grad(lambda q, k, v: jnp.sum(masked_flash(
                q, k, v, mask, d ** -0.5)[0].astype(f32)),
                argnums=(0, 1, 2))(q, k, v)
        kv = shape((b * (h if part.endswith("repeated") else hkv), seq, d))
        text = _compile(both, shape((b * h, seq, d)), kv, kv, mask)
        names = ["mxtpu_dsa_attn_fwd", "mxtpu_dsa_attn_bwd"]
        group = 1 if part.endswith("repeated") else h // hkv
        assert telemetry.value("flash.bwd.heads_per_kv_block") == group
    else:
        def grads(q, k, lse, qi, ki, w, mask, lse_i):
            return jax.value_and_grad(
                lambda qi, ki, w: jnp.sum(sa._index_loss_sum(
                    q, k, lse, qi, ki, w, mask, lse_i, d ** -0.5, 1 / 32)),
                argnums=(0, 1, 2))(qi, ki, w)
        text = _compile(grads, shape((b, h, seq, d)), shape((b, hkv, seq, d)),
                        shape((b, h, seq), f32), *indexer, mask,
                        shape((b, seq), f32))
        names = ["mxtpu_dsa_align_loss", "mxtpu_dsa_align_loss_grad"]
    for name in names:
        assert re.search(name + r"\b", text), name
    assert text.count("tpu_custom_call") == len(names)
    assert " while(" not in text


@pytest.mark.parametrize("seq,d,dv,causal", [(8192, 192, 128, True)],
                         ids=["nope-mla-s8192"])
def test_flash_kernels_at_the_kimi_mla_shape(one_chip, seq, d, dv, causal):
    """The kimi cell's one latent-attention layer: one sequence of 8192, 32
    heads at 192 / 128 — the backward kernel still holds a row's float32 dQ
    in VMEM there, so no scan is left."""
    from mxnet_tpu.ops import flash_attention

    def shape(d):
        return jax.ShapeDtypeStruct((1, 32, seq, d), jnp.bfloat16,
                                    sharding=one_chip)

    def grads(q, k, v):
        return jax.grad(lambda *a: flash_attention(*a, causal=causal).astype(
            jnp.float32).sum(), argnums=(0, 1, 2))(q, k, v)
    text = _compile(grads, shape(d), shape(d), shape(dv))
    calls = [ln for ln in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in ln]
    assert sorted(re.search(r'op_name="[^"]*(mxtpu_flash_\w+)', ln).group(1)
                  for ln in calls) == ["mxtpu_flash_bwd", "mxtpu_flash_fwd"]
    assert " while(" not in text


def test_scan_kernels_at_the_kimi_shape(one_chip):
    """The kimi cell's chunked scan: one sequence of 8192, 32 heads of 128 /
    128, chunks of 64, bfloat16 operands with the log-decay and beta
    float32, q and k normalised inside the tiles as the step has them (the
    op has no other variant) — the forward kernel and the backward kernel,
    one Mosaic call each, by name (neither name carries ``mxtpu_flash`` or ``mxtpu_gmm``),
    and no XLA scan left."""
    from mxnet_tpu.ops.linear_attention import kda_attention
    b, t, h, d = 1, 8192, 32, 128

    def shape(dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    def grads(q, k, v, g, beta):
        return jax.grad(lambda *a: kda_attention(*a)[0].astype(
            jnp.float32).sum(), argnums=(0, 1, 2, 3, 4))(q, k, v, g, beta)
    heads = shape((b, t, h, d))
    text = _compile(grads, heads, heads, heads,
                    shape((b, t, h, d), jnp.float32),
                    shape((b, t, h), jnp.float32))
    calls = [ln for ln in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in ln]
    names = sorted(re.search(r'op_name="[^"]*(mxtpu_\w+)', ln).group(1)
                   for ln in calls)
    assert names == ["mxtpu_kda_bwd", "mxtpu_kda_fwd"]
    assert not re.search(r"mxtpu_(flash|gmm)", text)
    assert " while(" not in text


def test_block_diffusion_kernels_at_the_sdar_shape(one_chip):
    """The SDAR cell's attention: a training row of 2 x 8192 positions, 32
    query / 4 key-value heads of 128, blocks of 4 — the clean half's
    block-causal call and the noisy half's offset call, each a forward and
    a backward kernel by the block rule's own names, the 8 query heads of a
    kv head reading it in place in both (the backward's programs at
    512-blocks, Q blocks outer, the kv head's float32 dK / dV rows in
    VMEM), and no scan left."""
    from mxnet_tpu import telemetry
    from mxnet_tpu.ops.flash_attention import block_diffusion_attention

    def shape(rows):
        return jax.ShapeDtypeStruct((rows, 2 * 8192, 128), jnp.bfloat16,
                                    sharding=one_chip)

    def grads(q, k, v):
        return jax.grad(lambda *a: block_diffusion_attention(
            *a, 4, 128 ** -0.5).astype(jnp.float32).sum(),
            argnums=(0, 1, 2))(q, k, v)
    text = _compile(grads, shape(32), shape(4), shape(4))
    calls = [ln for ln in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in ln]
    names = sorted(re.search(r'op_name="[^"]*(mxtpu_\w+)', ln).group(1)
                   for ln in calls)
    assert names == ["mxtpu_bd_attn_bwd"] * 2 + ["mxtpu_bd_attn_fwd"] * 2
    assert telemetry.value("flash.fwd.heads_per_kv_block") == 8
    assert telemetry.value("flash.bwd.heads_per_kv_block") == 8
    assert "mxtpu_flash" not in text
    assert " while(" not in text


@pytest.mark.parametrize("tokens,k,d,rows", [
    pytest.param(8192, 6, 2048, 12288, id="kanana"),
    pytest.param(16384, 8, 2048, 32768, id="keye"),
    pytest.param(8192, 8, 2304, 16384, id="kimi"),
    pytest.param(16384, 8, 2048, 65536, id="sdar"),
])
def test_token_side_sum_at_the_four_expert_cells(one_chip, tokens, k, d,
                                                 rows):
    """The expert layer's token-side sum at each cell's (tokens, top_k,
    hidden, buffer rows): the combine (weighted) and the dispatch's
    transpose, each the copy of the routed rows into slabs and the sum,
    by name, and no gather of rows left."""
    from mxnet_tpu.ops.moe_sum_rows import sum_rows

    def shaped(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def both(buffer, row_of_choice, here, weights, routed):
        return (sum_rows(buffer, row_of_choice, here, weights, routed),
                sum_rows(buffer, row_of_choice, here, None, routed))
    text = _compile(both, shaped((rows, d), jnp.bfloat16),
                    shaped((tokens, k), jnp.int32),
                    shaped((tokens, k), jnp.bool_),
                    shaped((tokens, k), jnp.float32),
                    shaped((), jnp.int32))
    calls = [ln for ln in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in ln]
    names = sorted(re.search(r'op_name="[^"]*(mxtpu_\w+)', ln).group(1)
                   for ln in calls)
    assert names == ["mxtpu_moe_sum_rows"] * 2 + \
        ["mxtpu_moe_sum_rows_slabs"] * 2
    assert not re.search(rf"= bf16\[\d+,{d}\]\S* (gather|fusion)\(", text)
