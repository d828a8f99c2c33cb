"""Block-diffusion attention (``ops/flash_attention.py`` under a block rule:
``mxtpu_bd_attn_fwd`` / ``mxtpu_bd_attn_bwd``): device time from the trace,
operations and bytes from the shapes — **the mathematics of a layer's pass,
not an implementation**.  A pass's operations are those of the (query, key)
pairs the mask leaves visible in a training row's two halves — a clean query
``p`` sees ``b (p // b + 1)`` keys, a noisy one ``b (p // b)`` clean keys and
the ``b`` noisy keys of its own block, ``b`` the block length — Q.K and P.V
over ``head_dim`` for every head forward, twice that backward (dV, dP, dQ,
dK).  Its bytes are q, k, v, o and the log-sum-exp moved once (the backward:
those and dO in, dQ, dK, dV out).  So two kernels and an XLA tile, or one
kernel, read the same work.

A pass is counted from the configuration — ``num_hidden_layers``, two
forward passes a layer under ``remat`` and one backward — not from how many
calls the trace holds: the share is the least time the chip could take over
a step's passes over the device time a step spends in the operations that
carry the name.  Where the trace has no such operation (a program without
the kernels, as the parent of the PR that brought them) the readers return
``None`` and the metric is left out."""

from readers import kda, roofline

BD_FWD = "mxtpu_bd_attn_fwd"
BD_BWD = "mxtpu_bd_attn_bwd"
_ITEMSIZE = {"bfloat16": 2, "float32": 4}


def visible_pairs(seq, block):
    """The pairs one half's queries see: ``sum_p b (p // b + 1)``, clean
    and noisy alike (``b (p // b)`` clean keys and ``b`` noisy ones)."""
    blocks = seq // block
    return block * block * blocks * (blocks + 1) // 2


def _shapes(sizes, traffic):
    seq, batch = traffic["seq_len"], traffic["per_chip_batch"]
    return dict(
        tokens=2 * batch * seq,
        pairs=2 * batch * visible_pairs(seq, sizes["block_length"]),
        h=sizes["num_attention_heads"], hkv=sizes["num_key_value_heads"],
        d=sizes["head_dim"], item=_ITEMSIZE[sizes["dtype"]],
        layers=sizes["num_hidden_layers"],
        forwards=2 if sizes.get("remat") else 1)


def bd_attn_fwd_cost(sizes, traffic):
    """``(flops, bytes)`` of one layer's forward over both halves: Q.K and
    P.V over ``head_dim`` for every head at the visible pairs; q and o at
    the query heads, k and v at the key-value heads, the float32
    log-sum-exp."""
    s = _shapes(sizes, traffic)
    flops = 2 * 2 * s["pairs"] * s["h"] * s["d"]
    nbytes = s["tokens"] * (s["d"] * s["item"] * (2 * s["h"] + 2 * s["hkv"])
                            + 4 * s["h"])
    return flops, nbytes


def bd_attn_bwd_cost(sizes, traffic):
    """``(flops, bytes)`` of one layer's backward: twice the forward's
    operations; q, o, dO in and dQ out at the query heads, k, v in and dK,
    dV out at the key-value heads, the log-sum-exp in."""
    s = _shapes(sizes, traffic)
    flops = 2 * 2 * 2 * s["pairs"] * s["h"] * s["d"]
    nbytes = s["tokens"] * (s["d"] * s["item"] * (4 * s["h"] + 4 * s["hkv"])
                            + 4 * s["h"])
    return flops, nbytes


def bd_attn_fwd_ms(ctx):
    return roofline.ms_per_step(ctx, BD_FWD)


def bd_attn_fwd_roofline(ctx):
    s = _shapes(ctx.sizes, ctx.traffic)
    return kda._share(ctx, "kernel.bd_attn_fwd_roofline", BD_FWD,
                      bd_attn_fwd_cost, s["layers"] * s["forwards"])


def bd_attn_bwd_ms(ctx):
    return roofline.ms_per_step(ctx, BD_BWD)


def bd_attn_bwd_roofline(ctx):
    s = _shapes(ctx.sizes, ctx.traffic)
    return kda._share(ctx, "kernel.bd_attn_bwd_roofline", BD_BWD,
                      bd_attn_bwd_cost, s["layers"])
