"""The expert layer's grouped matrix product (``mxtpu_gmm`` and its two
backward kernels, ``mxtpu_gmm_dlhs`` and ``mxtpu_gmm_drhs``): device time
from the trace, operations and bytes from the shapes and the rows the run
routed.  The trace cannot say how many rows a call had, so the runner counts
them with the reference's router on the first batch, before the first step
and after the window, and leaves them in ``sizes["routed_rows"]``
(``runners/train_fused_grads.py``); a product is costed at their mean over
the expert layers and the two counts, and the counts and the uniform share go
on the ``# rows.`` line.  Under a runner that leaves none, the uniform share
is the yardstick.  Where the trace has no such operation (the
``lax.ragged_dot`` fallback, or a program without the layer) the readers
return ``None`` and the metric is left out."""

from readers import roofline

GMM = "mxtpu_gmm"
_ITEMSIZE = {"bfloat16": 2, "float32": 4}


def routed_rows(sizes, traffic):
    """``(rows a product, uniform rows)``: the mean of what the runner
    counted where it counted, else the uniform share, tokens x
    ``num_experts_per_tok`` x held / routed."""
    tokens = traffic["per_chip_batch"] * traffic["seq_len"]
    uniform = tokens * sizes["num_experts_per_tok"] \
        * sizes["n_routed_experts"] / sizes["published"]["n_routed_experts"]
    counted = [r for rows in sizes.get("routed_rows", {}).values()
               for r in rows if r]          # a dense layer counts 0
    return (sum(counted) / len(counted) if counted else uniform), uniform


def moe_gmm_cost(sizes, traffic):
    """``(flops, bytes)`` of one grouped product of one expert layer: each
    routed row a multiply-accumulate over hidden x expert width (the same
    for the gate, up and down products and for each of their two backward
    products); the rows are read and the result rows written once (hidden
    wide on one side, expert-wide on the other) and the held experts'
    weights read or written once, in the compute dtype."""
    rows, _ = routed_rows(sizes, traffic)
    held = sizes["n_routed_experts"]
    d, w = sizes["hidden_size"], sizes["moe_intermediate_size"]
    flops = 2 * rows * d * w
    nbytes = (rows * (d + w) + held * d * w) * _ITEMSIZE[sizes["dtype"]]
    return flops, nbytes


def moe_gmm_ms(ctx):
    """Device time per step of the operations whose name carries
    ``mxtpu_gmm``: forward and both backward products, and the forward again
    where a layer is recomputed."""
    return roofline.ms_per_step(ctx, GMM)


def moe_gmm_roofline(ctx):
    """The least time the chip could take for the calls the trace holds, at
    the rows the run routed, over the time they took."""
    share = roofline.roofline_pct(ctx, "kernel.moe_gmm_roofline", GMM,
                                  moe_gmm_cost)
    if share is not None:
        rows, uniform = routed_rows(ctx.sizes, ctx.traffic)
        ctx.note("rows.kernel.moe_gmm_roofline",
                 {"costed_at": rows, "uniform": uniform,
                  "counted": ctx.sizes.get("routed_rows")})
    return share
