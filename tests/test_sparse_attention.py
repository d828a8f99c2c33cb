"""``ops/sparse_attention.py``: the exact k-th largest against ``lax.top_k``,
the selection with ties and short pasts, and each Pallas kernel (in the
interpreter) against its XLA form and against a dense masked softmax."""
import importlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import lax

from mxnet_tpu import telemetry
from mxnet_tpu.ops import sparse_attention as sa
from mxnet_tpu.ops.flash_attention import masked_flash
from mxnet_tpu.ops.kernel_mode import interpret_kernels


def _rand(seed, *shape):
    return jnp.asarray(np.random.RandomState(seed).randn(*shape),
                       jnp.float32)


@pytest.mark.parametrize("case", ["random", "ties", "negative", "k-is-n",
                                  "k-is-1", "infinities"])
def test_kth_largest_is_exact(case):
    rng = np.random.RandomState(0)
    x = rng.randn(16, 200).astype(np.float32)
    k = np.full(16, 17, np.int32)
    if case == "ties":
        x = np.round(x * 2) / 2         # a dozen values, many times each
    elif case == "negative":
        x = -np.abs(x) - 1
    elif case == "k-is-n":
        k[:] = 200
    elif case == "k-is-1":
        k[:] = 1
    elif case == "infinities":
        x[:, ::7] = -np.inf
        x[:, 3] = np.inf
    k[::3] = np.minimum(k[::3] + 5, 200)        # k varies by row
    got = np.asarray(sa.kth_largest(jnp.asarray(x), jnp.asarray(k)))
    ordered = np.asarray(lax.top_k(jnp.asarray(x), 200)[0])
    want = ordered[np.arange(16), k - 1]
    assert (got == want).all()          # the value itself, bit for bit


def _dense_selection(scores, topk):
    """(L, L) bool, queries first: by ``lax.top_k``'s threshold."""
    n = scores.shape[0]
    causal = jnp.tril(jnp.ones((n, n), bool))
    ordered = lax.top_k(jnp.where(causal, scores, -jnp.inf), n)[0]
    tau = ordered[jnp.arange(n), jnp.minimum(topk, jnp.arange(n) + 1) - 1]
    return causal & (scores >= tau[:, None])


@pytest.mark.parametrize("mode", ["xla", "interpret"])
def test_selection_against_top_k_with_ties_and_short_pasts(mode):
    """Rows with ``t < k`` take their whole past; a tie at the threshold
    takes every key that ties (one index head with weights of 0 and 1 and
    few distinct products gives many)."""
    seq, topk, hi, di = 256, 16, 2, 64
    rng = np.random.RandomState(1)
    qi = jnp.asarray(rng.randint(0, 2, (1, hi, seq, di)), jnp.float32)
    ki = jnp.asarray(rng.randint(0, 2, (1, seq, di)), jnp.float32)
    w = jnp.asarray(rng.randint(0, 3, (1, seq, hi)), jnp.float32)
    scale = 0.125
    if mode == "interpret":
        with interpret_kernels():
            mask, tau, lse = sa._index_select(qi, ki, w, topk, scale)
    else:
        mask, tau, lse = sa._index_select(qi, ki, w, topk, scale)
    scores = scale * jnp.einsum(
        "hqk,qh->qk", jax.nn.relu(jnp.einsum("hqd,kd->hqk", qi[0], ki[0])),
        w[0])
    want = np.asarray(_dense_selection(scores, topk))
    got = np.asarray(mask[0]).T != 0
    assert (got == want).all()
    kept = got.sum(1)
    assert (kept[:topk] == np.arange(1, topk + 1)).all()    # the whole past
    assert kept.max() > topk                                # a tie took both
    assert (kept[topk:] >= topk).all()
    want_lse = jax.nn.logsumexp(jnp.where(want, scores, -jnp.inf), axis=-1)
    np.testing.assert_allclose(lse[0], want_lse, rtol=1e-6)
    ordered = np.asarray(lax.top_k(jnp.where(np.tril(np.ones((seq, seq),
                                                             bool)),
                                             scores, -jnp.inf), seq)[0])
    assert (np.asarray(tau[0]) == ordered[
        np.arange(seq), np.minimum(topk, np.arange(seq) + 1) - 1]).all()


def _operands(seed, b, h, hkv, seq, d, hi, di):
    return (_rand(seed, b, h, seq, d), _rand(seed + 1, b, hkv, seq, d),
            _rand(seed + 2, b, hkv, seq, d), _rand(seed + 3, b, hi, seq, di),
            _rand(seed + 4, b, seq, di), _rand(seed + 5, b, seq, hi))


def _dense(q, k, v, qi, ki, w, topk):
    """The whole op densely: (L, L) scores, a masked softmax, autodiff."""
    b, h, seq, d = q.shape
    hkv, hi, di = k.shape[1], qi.shape[1], qi.shape[3]
    scale = hi ** -0.5 * di ** -0.5
    scores = scale * jnp.einsum(
        "bhqk,bqh->bqk",
        jax.nn.relu(jnp.einsum("bhqd,bkd->bhqk", qi, ki)), w)
    sel = jnp.stack([_dense_selection(s, topk)
                     for s in lax.stop_gradient(scores)])
    kr, vr = (jnp.repeat(a, h // hkv, axis=1) for a in (k, v))
    logits = jnp.einsum("bhqd,bhkd->bhqk", q, kr) * d ** -0.5
    p = jax.nn.softmax(jnp.where(sel[:, None], logits, -jnp.inf), axis=-1)
    out = jnp.einsum("bhqk,bhkd->bhqd", p, vr)
    pbar = lax.stop_gradient(jnp.mean(p, axis=1))
    logq = jax.nn.log_softmax(jnp.where(sel, scores, -jnp.inf), axis=-1)
    kl = jnp.where(pbar > 0, pbar * (jnp.log(jnp.where(pbar > 0, pbar, 1.0))
                                     - jnp.where(sel, logq, 0.0)), 0.0)
    return out, jnp.sum(kl, axis=(1, 2)) / seq


def _both(fn, args):
    def scalar(*a):
        out, loss = fn(*a)
        weight = jnp.cos(jnp.arange(out.size, dtype=jnp.float32)
                         ).reshape(out.shape)
        return jnp.sum(out * weight) + 3.0 * jnp.sum(loss), (out, loss)
    return jax.jit(jax.grad(scalar, argnums=tuple(range(6)),
                            has_aux=True))(*args)


@pytest.mark.parametrize("seq,topk,mode", [
    pytest.param(256, 40, "interpret", id="one-pass-kernels"),
    pytest.param(384, 64, "interpret", id="streaming-3-blocks-kernels"),
    pytest.param(96, 10, "xla", id="xla-forms-no-128-multiple"),
    pytest.param(512, 64, "xla", id="xla-forms-two-query-blocks"),
])
def test_sparse_attention_forward_and_backward_against_dense(seq, topk, mode):
    """Output, index loss and all six gradients: the four kernels in the
    interpreter (or the XLA forms) against a dense masked softmax under
    autodiff.  q, k, v get their gradient from the output alone, the
    indexer's operands from the loss alone."""
    args = _operands(10, 2, 4, 2, seq, 64, 2, 64)
    telemetry.reset()
    if mode == "interpret":
        with interpret_kernels():
            grads, (out, loss) = _both(
                lambda *a: sa.sparse_gq_attention(*a, topk), args)
        assert telemetry.value("dsa.attn.fwd.pallas") == 1
        assert telemetry.value("dsa.attn.bwd.pallas") == 1
        assert telemetry.value("dsa.index.pallas") == 1
        assert telemetry.value("dsa.index_loss.pallas") == 1
    else:
        grads, (out, loss) = _both(
            lambda *a: sa.sparse_gq_attention(*a, topk), args)
        assert telemetry.value("dsa.attn.fwd.scan") == 1
        assert telemetry.value("dsa.index.xla") == 1
    assert telemetry.value("dsa.select.radix") == 1
    assert telemetry.value("dsa.selected_share") == pytest.approx(
        sa.selected_share(seq, topk))
    want_grads, (want_out, want_loss) = _both(
        lambda *a: _dense(*a, topk), args)
    np.testing.assert_allclose(out, want_out, atol=2e-5)
    np.testing.assert_allclose(loss, want_loss, rtol=2e-5)
    for name, got, want in zip("q k v qi ki w".split(), grads, want_grads):
        assert float(jnp.abs(got - want).max()) <= \
            2e-4 * float(jnp.abs(want).max()), name
    if mode == "interpret":
        # a forward nobody differentiates takes the value kernel alone: the
        # same loss
        with interpret_kernels():
            plain = sa.sparse_gq_attention(*args, topk)[1]
        np.testing.assert_allclose(plain, loss, rtol=1e-6)


def _loss_kernel_operands(seq, topk):
    """The alignment loss's operands as ``sparse_gq_attention`` hands them
    on: the selection and both log-sum-exps from the XLA forms."""
    q, k, v, qi, ki, w = _operands(20, 2, 4, 2, seq, 64, 2, 64)
    sm_scale, scale = 64 ** -0.5, 2 ** -0.5 * 64 ** -0.5
    mask, _, lse_i = sa._index_select(qi, ki, w, topk, scale)
    rows = (2 * 4, seq, 64)
    _, lse = masked_flash(q.reshape(rows), jnp.repeat(k, 2, 1).reshape(rows),
                          jnp.repeat(v, 2, 1).reshape(rows), mask, sm_scale)
    return (q, k, lse.reshape(2, 4, seq), qi, ki, w, mask, lse_i), \
        (sm_scale, scale)


@pytest.mark.parametrize("seq,topk", [
    pytest.param(256, 40, id="one-tile"),
    pytest.param(384, 64, id="3x3-tiles-of-128"),
])
@pytest.mark.parametrize("kernel", ["value", "gradient"])
def test_alignment_loss_kernels_against_the_xla_form(kernel, seq, topk):
    """Each of the two kernels alone (in the interpreter) against
    ``jax.value_and_grad`` of ``_xla_index_loss``: the value kernel's
    ``kl`` summed a sequence, the gradient kernel's ``dqi``, ``dki``,
    ``dw``.  With three blocks a side the dead tiles, a row's several key
    blocks and ``dki`` gathered over the query blocks are all there."""
    (q, k, lse, qi, ki, w, mask, lse_i), scales = \
        _loss_kernel_operands(seq, topk)
    want_kl, want_grads = jax.vmap(jax.value_and_grad(
        lambda qi, ki, w, q, k, lse, mask: sa._xla_index_loss(
            q, k, lse, qi, ki, w, mask, *scales), argnums=(0, 1, 2)))(
                qi, ki, w, q, jnp.repeat(k, 2, axis=1), lse, mask)
    laid_out = sa._loss_layout(q, k, qi, ki, w)
    args = (*laid_out[:2], lse, *laid_out[2:], mask, lse_i, *scales)
    if kernel == "value":
        kl = sa._pallas_index_loss(*args, interpret=True)
        assert kl.shape == (2, seq) and kl.dtype == jnp.float32
        np.testing.assert_allclose(jnp.sum(kl, axis=1), want_kl, rtol=2e-5)
        return
    dqi, dki, dw = sa._pallas_index_loss_grad(*args, interpret=True)
    for name, got, want in zip(
            ("qi", "ki", "w"),
            (jnp.swapaxes(dqi, 2, 3), jnp.swapaxes(dki, 1, 2),
             jnp.swapaxes(dw, 1, 2)), want_grads):
        assert got.dtype == jnp.float32 and got.shape == want.shape, name
        assert float(jnp.abs(got - want).max()) <= \
            2e-4 * float(jnp.abs(want).max()), name


def _pallas_calls(jaxpr, inside=()):
    """``(kernel name, the primitives it is nested in)`` of every
    ``pallas_call`` of a jaxpr, sub-jaxprs included."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found.append((eqn.params["name"], inside))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += _pallas_calls(sub, inside + (eqn.primitive.name,))
    return found


def test_remat_runs_one_value_and_one_gradient_kernel_a_layer():
    """``jax.value_and_grad`` of two ``jax.checkpoint`` layers around the
    op, after dead-code elimination: the value kernel once a layer, in the
    first pass (nothing of the recomputation reads its ``kl``, so it is not
    there), and the gradient kernel once a layer, in the backward."""
    from jax._src.interpreters import partial_eval as pe
    args = _operands(30, 1, 2, 1, 128, 64, 2, 64)

    @jax.checkpoint
    def layer(x, q, *rest):
        out, loss = sa.sparse_gq_attention(q + x, *rest, 16)
        return jnp.mean(out), jnp.sum(loss)

    def two_layers(*a):
        x, total = 0.0, 0.0
        for _ in range(2):
            x, loss = layer(x, *a)
            total = total + loss
        return x + total
    telemetry.reset()
    with interpret_kernels():
        closed = jax.make_jaxpr(jax.value_and_grad(
            two_layers, argnums=tuple(range(6))))(*args)
    jaxpr, _ = pe.dce_jaxpr(closed.jaxpr, [True] * len(closed.jaxpr.outvars))
    calls = _pallas_calls(jaxpr)
    value = [inside for name, inside in calls
             if name == "mxtpu_dsa_align_loss"]
    grad = [inside for name, inside in calls
            if name == "mxtpu_dsa_align_loss_grad"]
    assert len(value) == 2 and len(grad) == 2, calls
    # the recomputation lives in the backward's remat equation
    assert all("remat2" in inside for inside in grad), grad
    assert not any("remat2" in inside for inside in value), value
    # the other forward kernels are what the recomputation does run again
    assert sum(name == "mxtpu_dsa_attn_fwd" for name, _ in calls) == 4
    # the counters count traces, not what runs: the value kernel in the
    # custom VJP's primal and again in its forward rule, the gradient kernel
    # in the backward rule, once a layer
    assert telemetry.value("dsa.index_loss.pallas") == \
        telemetry.value("dsa.layers")
    assert telemetry.value("dsa.index_loss.grad.pallas") == 2
    assert telemetry.value("dsa.index_loss.value.pallas") >= 2


def test_masked_flash_rows_share_their_batch_mask():
    """Two batches with different masks in one call: a program's rows are
    one batch's heads, so each batch reads its own mask."""
    b, h, seq, d = 2, 4, 256, 64
    q, k, v = (_rand(s, b * h, seq, d) for s in (1, 2, 3))
    rng = np.random.RandomState(4)
    keep = np.tril(rng.rand(b, seq, seq) < 0.3) | np.eye(seq, dtype=bool)
    mask = jnp.asarray(keep.transpose(0, 2, 1), jnp.int8)      # keys first
    with interpret_kernels():
        out, lse = masked_flash(q, k, v, mask, d ** -0.5)
    logits = jnp.einsum("rqd,rkd->rqk", q, k) * d ** -0.5
    sel = jnp.repeat(jnp.asarray(keep), h, axis=0)
    p = jax.nn.softmax(jnp.where(sel, logits, -jnp.inf), axis=-1)
    np.testing.assert_allclose(out, jnp.einsum("rqk,rkd->rqd", p, v),
                               atol=2e-5)
    np.testing.assert_allclose(
        lse, jax.nn.logsumexp(jnp.where(sel, logits, -jnp.inf), axis=-1),
        rtol=1e-5, atol=1e-5)


def _two_masks(seed, b, seq):
    """(B, L, L) selections, queries first, each batch its own (the
    diagonal kept), and the same keys first as the kernels take them."""
    rng = np.random.RandomState(seed)
    keep = np.tril(rng.rand(b, seq, seq) < 0.3) | np.eye(seq, dtype=bool)
    return keep, jnp.asarray(keep.transpose(0, 2, 1), jnp.int8)


def _grouped_dense(q, k, v, keep, group, d):
    """out and lse of a masked softmax, query row ``r`` reading kv row
    ``r // group`` (the rows batch-major, a batch's heads together)."""
    kr, vr = (jnp.repeat(a, group, axis=0) for a in (k, v))
    sel = jnp.repeat(jnp.asarray(keep), q.shape[0] // keep.shape[0], axis=0)
    logits = jnp.where(sel, jnp.einsum("rqd,rkd->rqk", q, kr) * d ** -0.5,
                       -jnp.inf)
    p = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("rqk,rkd->rqd", p, vr), jax.nn.logsumexp(logits, -1)


@pytest.mark.parametrize("group", [8, 4, 1])
def test_masked_flash_reads_each_kv_head_in_place(group):
    """Grouped-query attention with K / V at their own heads — a forward
    program of the query heads of one kv head, one K / V block and one mask
    tile for them all, and a backward program of all of them, Q blocks
    outer, the kv head's dK / dV summed in VMEM — against the same call with
    K / V repeated to the query heads and against a dense masked softmax:
    out, the log-sum-exp and the gradients of q, k, v (dK / dV summed over
    a kv head's query heads).  Two batches with masks of their own; at
    L = 1024 the backward of a group of 8 or 4 walks two Q blocks of 512
    over two KV blocks (one dead step a kv head, the dK / dV of the first
    KV block written out on the last Q block's walk), a group of 1 takes
    the parent's program."""
    b, h, seq, d = 2, 8, 1024, 64
    hkv = h // group
    q = _rand(40, b * h, seq, d)
    k, v = _rand(41, b * hkv, seq, d), _rand(42, b * hkv, seq, d)
    keep, mask = _two_masks(43, b, seq)
    weight = jnp.cos(jnp.arange(seq * d, dtype=jnp.float32)).reshape(seq, d)

    def run(kv_rows):
        def f(q, k, v):
            out, lse = masked_flash(q, kv_rows(k), kv_rows(v), mask,
                                    d ** -0.5)
            return jnp.sum(out * weight), (out, lse)
        return jax.grad(f, argnums=(0, 1, 2), has_aux=True)(q, k, v)

    telemetry.reset()
    with interpret_kernels():
        grads, (out, lse) = run(lambda x: x)
        assert telemetry.value("flash.fwd.heads_per_kv_block") == group
        assert telemetry.value("flash.bwd.heads_per_kv_block") == group
        assert telemetry.value("flash.bwd.rows_per_program") == (
            group if group > 1 else 1)
        rep_grads, (rep_out, rep_lse) = run(
            lambda x: jnp.repeat(x, group, axis=0))
        assert telemetry.value("flash.fwd.heads_per_kv_block") == 1
        assert telemetry.value("flash.bwd.heads_per_kv_block") == 1
    want_out, want_lse = _grouped_dense(q, k, v, keep, group, d)
    for got in (out, rep_out):
        np.testing.assert_allclose(got, want_out, atol=2e-5)
    for got in (lse, rep_lse):
        np.testing.assert_allclose(got, want_lse, rtol=1e-5, atol=1e-5)
    want_grads = jax.grad(lambda q, k, v: jnp.sum(
        _grouped_dense(q, k, v, keep, group, d)[0] * weight),
        argnums=(0, 1, 2))(q, k, v)
    for name, got, rep, want in zip("qkv", grads, rep_grads, want_grads):
        assert got.shape == want.shape, name
        np.testing.assert_allclose(got, rep, atol=2e-5, err_msg=name)
        np.testing.assert_allclose(got, want, atol=1e-4, err_msg=name)


def test_a_chip_keeps_its_query_heads_with_their_kv_heads():
    """Under an ambient dp mesh the kernels go inside a shard_map over the
    leading dimension of q (B·H rows), of k / v (B·Hkv) and of the mask (B):
    a chip's query rows are whole batches, so the kv rows they read are the
    chip's own.  The split result is the unsplit one; where the kv rows do
    not divide by the data axis nothing is split."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from mxnet_tpu.parallel import make_mesh, mesh_scope
    fa = importlib.import_module("mxnet_tpu.ops.flash_attention")
    b, h, hkv, seq, d = 2, 8, 2, 256, 64
    q = _rand(50, b * h, seq, d)
    k, v = _rand(51, b * hkv, seq, d), _rand(52, b * hkv, seq, d)
    _, mask = _two_masks(53, b, seq)
    mesh = make_mesh({"dp": 2}, devices=jax.devices()[:2])

    def grads(scope):
        def loss(q, k, v):
            with mesh_scope(scope):
                out, _ = masked_flash(q, k, v, mask, d ** -0.5)
            return jnp.sum(out ** 2), out
        return jax.grad(loss, argnums=(0, 1, 2), has_aux=True)

    with interpret_kernels():
        split = str(jax.make_jaxpr(grads(mesh))(q, k, v))
        sharded = [jax.device_put(a, NamedSharding(mesh, P("dp")))
                   for a in (q, k, v)]
        got, out = jax.jit(grads(mesh))(*sharded)
        want, want_out = jax.jit(grads(None))(q, k, v)
    assert split.count("shard_map") == 2
    np.testing.assert_allclose(out, want_out, atol=1e-6)
    for a, c in zip(got, want):
        np.testing.assert_allclose(a, c, atol=1e-5)
    four, seen = make_mesh({"dp": 4}, devices=jax.devices()[:4]), []

    def probe(q, k):
        seen.extend([fa._dp_mesh(q), fa._dp_mesh(q, k)])
        return q
    with mesh_scope(four):
        jax.make_jaxpr(probe)(q, k[:2])
    assert seen == [four, None]         # 16 query rows, 2 kv rows, by 4


def test_selected_share_at_the_cell_shape():
    assert sa.selected_share(16384, 2048) == pytest.approx(0.2344, abs=1e-4)
    assert sa.selected_share(2048, 2048) == 1.0
