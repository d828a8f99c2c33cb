#!/usr/bin/env python3
"""Does the system still start on the chip?  One process, the normal entry
points, seeded synthetic inputs, nothing from the network.

    python3 chip_smoke.py

fails (non-zero exit, no result line) the moment JAX's first device is not a
TPU.  Otherwise it runs, in order: the device context; a ResNet-50 training
step the way the README writes it, then through ``DataParallelTrainer`` at
batch 128 in bf16; BERT-base training at batch 64, sequence 128, with the
Pallas flash kernel in the compiled step; every Pallas kernel against its XLA
reference at a production shape; a Llama-3-8B-width server (two layers)
answering eight requests through the engine and the continuous batcher; and,
where more than one chip is visible, both trainers over all of them, ZeRO-1
included.  No phase is caught: the first failure ends the run with its
traceback.  The last line of stdout is one JSON object,
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.

Times printed here are observations of one run on one device, not benchmark
numbers: ``first_call_s`` is trace + compile + one execution, ``steady_step_s``
the mean of the following steps, each window closed by ``asnumpy`` /
``block_until_ready``.

    python3 chip_smoke.py --rehearse-on-cpu

is a rehearsal of the control flow at toy sizes on the CPU backend, with the
Pallas kernels in the interpreter.  It is chosen by that argument only, never
inferred from a missing chip, says REHEARSAL in its output and its result
line, and measures nothing.
"""
from __future__ import annotations

import argparse
import contextlib
import gc
import json
import math
import os
import re
import sys
import time

import numpy as np

# One table of sizes for each way of running.  "chip" is the published width
# of every model (depth cut only for the 8B server, so that fp32 weights fit
# 16 GB); "rehearsal" is as small as the same code paths allow.
SIZES = {
    "chip": dict(
        classes=1000, image=224, readme_batch=32, resnet_batch=128,
        steady_steps=5,
        bert=dict(num_layers=12, units=768, hidden_size=3072, num_heads=12,
                  vocab_size=30522, max_length=128),
        bert_batch=64, bert_seq=128,
        # (B*H, L, D, Dv, causal): BERT-base, a long causal one, and latent
        # attention as the kanana cell calls it (Q.K over 192, V at 128)
        flash_shapes=((768, 128, 64, 64, (False, True)),
                      (8, 2048, 128, 128, (False, True)),
                      (64, 4096, 192, 128, (True,))),
        # (B*H, L, D, Dv, causal) of the backward kernel against the scan:
        # the attention calls of the benchmark's BERT and kanana cells
        flash_bwd_shapes=((1536, 128, 64, 64, False),
                          (384, 512, 64, 64, False),
                          (64, 4096, 192, 128, True)),
        # the kanana cell's expert products: a worst-case buffer of which an
        # eighth is routed, 16 experts held
        gmm=dict(rows=49152, routed=6144, groups=16, k=2048, n=768),
        # the expert layer around them at that cell's shape (8192 tokens
        # choose 6 of 128 experts, 16 held: a worst case of 49152 rows in
        # buffers of 12288) at fills of 1/32, 1/8 and over 1/4 of it
        moe=dict(tokens=8192, top_k=6, held=16, experts=128, d=2048, h=768,
                 routed=(1536, 6144, 14746)),
        # the keye cell's sparse attention: one sequence of 16384, 32 / 4
        # heads of 128, 16 index heads of 64, top-2048; the scans that the
        # attention kernels are held to take `check_rows` of the 32 rows
        dsa=dict(heads=32, kv_heads=4, seq=16384, d=128, index_heads=16,
                 index_dim=64, topk=2048, check_rows=2),
        # the kimi cell's scan: one sequence of 8192, 32 heads of 128 / 128;
        # the XLA form it is held to takes `check_heads` of the heads
        kda=dict(heads=32, seq=8192, d=128, check_heads=2),
        ln_rows=8192, ln_dim=768,
        bucket_elems=25_557_032,            # one ResNet-50 of parameters
        paged=dict(batch=8, heads=32, kv_heads=8, head_dim=128, block=16,
                   context=512),
        llama=dict(num_layers=2),           # every width is Llama-3-8B's
        serve=dict(max_batch=4, block_size=16, max_context=256),
        serve_prompts=(5, 37, 12, 90, 24, 3, 61, 17),
        serve_budgets=(8, 4, 16, 6, 12, 3, 9, 5),
    ),
    "rehearsal": dict(
        classes=10, image=32, readme_batch=4, resnet_batch=8,
        steady_steps=2,
        bert=dict(num_layers=1, units=64, hidden_size=128, num_heads=1,
                  vocab_size=128, max_length=128),
        bert_batch=2, bert_seq=128,
        flash_shapes=((2, 128, 64, 64, (False, True)),
                      (2, 128, 192, 128, (True,))),
        flash_bwd_shapes=((2, 128, 64, 64, False),
                          (2, 128, 192, 128, True)),
        gmm=dict(rows=256, routed=150, groups=4, k=128, n=128),
        moe=dict(tokens=128, top_k=2, held=2, experts=8, d=128, h=128,
                 routed=(8, 100, 200)),
        dsa=dict(heads=4, kv_heads=2, seq=256, d=64, index_heads=2,
                 index_dim=64, topk=32, check_rows=2),
        kda=dict(heads=2, seq=128, d=32, check_heads=2),
        ln_rows=32, ln_dim=128,
        bucket_elems=20_000,
        paged=dict(batch=2, heads=4, kv_heads=2, head_dim=64, block=8,
                   context=32),
        llama=dict(vocab_size=256, hidden_size=64, intermediate_size=128,
                   num_layers=1, num_heads=4, num_kv_heads=2,
                   max_seq_len=128),
        serve=dict(max_batch=2, block_size=8, max_context=16),
        serve_prompts=(5, 11, 3),
        serve_budgets=(4, 2, 5),
    ),
}

SEED = 20260926


def say(phase, msg):
    print(f"[{phase}] {msg}", flush=True)


def memory_line(phase):
    """``bytes_in_use`` / ``peak_bytes_in_use`` of every device.  The peak is
    the process's so far: it never falls between phases."""
    import jax
    for d in jax.devices():
        stats = d.memory_stats() or {}
        say(phase, f"memory device={d.id} "
                   f"bytes_in_use={stats.get('bytes_in_use')} "
                   f"peak_bytes_in_use={stats.get('peak_bytes_in_use')}")


def release():
    """Drop what the last phase left on the device before the next one
    allocates (16 GB holds one phase at a time, not all of them)."""
    gc.collect()


def assert_finite(name, arr):
    arr = np.asarray(arr, np.float32)
    assert np.all(np.isfinite(arr)), f"{name}: non-finite values {arr!r}"


def assert_close(name, got, want, tol):
    """max|got - want| <= tol * max(1, max|want|), both read as float32."""
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    assert got.shape == want.shape, f"{name}: {got.shape} vs {want.shape}"
    assert_finite(name, got)
    err = float(np.max(np.abs(got - want))) if got.size else 0.0
    scale = max(1.0, float(np.max(np.abs(want)))) if want.size else 1.0
    assert err <= tol * scale, \
        f"{name}: max abs err {err:.3e} > {tol:g} * {scale:.3e}"
    return err


def mosaic_calls(hlo_text, kernel_name):
    """Lines of compiled HLO that are Mosaic custom calls of ``kernel_name``:
    the ``name=`` its ``pallas_call`` carries is a component of the call's
    ``op_name``, bare or wrapped by a transform (``jvp(name)``,
    ``transpose(jvp(name))``)."""
    named = re.compile(rf'op_name="[^"]*\b{re.escape(kernel_name)}\b[^"]*"')
    return [ln for ln in hlo_text.splitlines()
            if 'custom_call_target="tpu_custom_call"' in ln
            and named.search(ln)]


def timed_steps(step, n):
    """(first_call_s, steady_step_s, losses): one call that compiles, then
    ``n`` more, each window closed by pulling the loss to the host."""
    t0 = time.perf_counter()
    losses = [float(step().asnumpy())]
    first = time.perf_counter() - t0
    t0 = time.perf_counter()
    pending = [step() for _ in range(n)]
    losses += [float(p.asnumpy()) for p in pending]
    steady = (time.perf_counter() - t0) / n
    return first, steady, losses


# ---------------------------------------------------------------------------
# phase 1: context
# ---------------------------------------------------------------------------

def phase_context(run):
    import jax
    import mxnet_tpu as mx
    ph = "1 context"
    ctx = run.ctx
    dev = ctx.jax_device
    assert dev.platform == run.platform, (dev, run.platform)
    assert dev == jax.devices()[0]
    assert mx.current_context() == ctx, mx.current_context()
    a = mx.nd.ones((8, 128))
    assert a.context == ctx, a.context
    assert a.data.devices() == {dev}, a.data.devices()
    assert float(a.asnumpy().sum()) == 1024.0
    # benchmark/peaks.json is the one table of peaks the repo keeps, looked
    # up by exact device_kind as benchmark/manifest.py does
    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "benchmark", "peaks.json")) as f:
        peak = json.load(f).get(dev.device_kind, {}).get("bf16_flops_per_s")
    say(ph, f"context={ctx} jax_device={dev} "
            f"chip_peak_flops[{dev.device_kind!r}]={peak}")
    if not run.rehearsal:
        assert mx.context.num_tpus() == len(jax.devices())
        assert peak is not None, \
            f"no peak FLOP/s for device_kind {dev.device_kind!r}"


# ---------------------------------------------------------------------------
# phase 2: ResNet-50 training
# ---------------------------------------------------------------------------

def _resnet(run):
    from mxnet_tpu.gluon.model_zoo import vision
    if run.rehearsal:
        # ResNet-50's bottleneck block, two thin stages of one block each
        return vision.ResNetV1(vision.BottleneckV1, [1, 1], [8, 16, 32],
                               classes=run.sizes["classes"])
    return vision.resnet50_v1()


def _images(run, batch, rng):
    import mxnet_tpu as mx
    s = run.sizes
    x = rng.uniform(-1, 1, (batch, 3, s["image"], s["image"]))
    y = rng.randint(0, s["classes"], (batch,))
    return (mx.nd.array(x.astype(np.float32)),
            mx.nd.array(y.astype(np.float32)))


def phase_resnet_readme(run):
    """README.md's own snippet, two steps, fp32."""
    import mxnet_tpu as mx
    from mxnet_tpu import autograd, gluon
    ph = "2a resnet README snippet"
    s = run.sizes
    batch = s["readme_batch"]
    lr = 0.1
    mx.random.seed(SEED)
    net = _resnet(run)
    net.initialize(ctx=run.ctx)
    net.hybridize()
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": lr}, kvstore="dist_tpu_sync")
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    x, y = _images(run, batch, np.random.RandomState(SEED))
    losses, times = [], []
    for i in range(2):
        t0 = time.perf_counter()
        with autograd.record():
            loss = loss_fn(net(x), y)
        loss.backward()
        if i == 0:
            # plain SGD: the step must be exactly w - lr * grad / batch.
            # The output bias is small enough to check on the host.
            name, bias = [(n, p) for n, p in net.collect_params().items()
                          if n.endswith("bias")][-1]
            assert bias.shape == (s["classes"],), (name, bias.shape)
            w0 = bias.data().asnumpy().copy()
            g0 = bias.grad().asnumpy().copy()
        trainer.step(batch)
        losses.append(float(loss.asnumpy().mean()))
        times.append(time.perf_counter() - t0)
        if i == 0:
            w1 = bias.data().asnumpy()
            assert np.any(g0 != 0), f"{name}: zero gradient"
            err = assert_close(f"sgd step of {name}", w1,
                               w0 - lr * g0 / batch, 1e-6)
            say(ph, f"update check {name}: max|w1-(w0-lr*g/B)|={err:.2e}")
    for l in losses:
        assert_finite("loss", l)
    assert losses[0] != losses[1], "loss did not move after an update"
    assert next(iter(net.collect_params().values())).data().context == run.ctx
    say(ph, f"model={type(net).__name__} batch={batch} fp32 "
            f"first_call_s={times[0]:.2f} second_call_s={times[1]:.2f} "
            f"losses={[round(l, 4) for l in losses]}")
    memory_line(ph)


def _update_rule_reference(run, optimizer, opt_args):
    """Three ``DataParallelTrainer`` steps on a 6x4 Dense layer against the
    same rule written out in float64 numpy: the assertion a broken fused
    update fails, on an input small enough to do by hand."""
    import mxnet_tpu as mx
    from mxnet_tpu import gluon
    from mxnet_tpu.parallel import make_mesh
    from mxnet_tpu.parallel.data_parallel import DataParallelTrainer
    import jax
    rng = np.random.RandomState(SEED)
    B, I, O = 8, 6, 4
    x = rng.randn(B, I).astype(np.float32)
    t = rng.randn(B, O).astype(np.float32)
    net = gluon.nn.Dense(O, in_units=I)
    net.initialize(ctx=run.ctx)
    w = net.weight.data().asnumpy().astype(np.float64)
    b = net.bias.data().asnumpy().astype(np.float64)
    mesh = make_mesh({"dp": 1}, devices=jax.devices()[:1])
    tr = DataParallelTrainer(net, gluon.loss.L2Loss(), optimizer,
                             dict(opt_args), mesh=mesh)
    lr = opt_args["learning_rate"]
    state = {}
    for step in range(1, 4):
        # float32 products on the MXU (the default rounds them to bf16, which
        # alone is 1e-4 here), so that the tolerance can be float32's
        with jax.default_matmul_precision("highest"):
            tr.step(mx.nd.array(x), mx.nd.array(t)).asnumpy()
        # L2Loss is mean_j 0.5*(p-t)^2 per sample; the step takes the batch
        # mean of it
        d = (x.astype(np.float64) @ w.T + b - t) / (B * O)
        for key, p, g in (("w", w, d.T @ x), ("b", b, d.sum(0))):
            if optimizer == "sgd":
                m = state.get(key, 0.0) * opt_args["momentum"] - lr * g
                state[key] = m
                p += m
            else:   # adam, MXNet's form: bias correction folded into lr
                m, v = state.get(key, (0.0, 0.0))
                m = 0.9 * m + 0.1 * g
                v = 0.999 * v + 0.001 * g * g
                state[key] = (m, v)
                lr_t = lr * math.sqrt(1 - 0.999 ** step) / (1 - 0.9 ** step)
                p -= lr_t * m / (np.sqrt(v) + 1e-8)
    errs = (assert_close(f"{optimizer} weight", net.weight.data().asnumpy(),
                         w, 1e-5),
            assert_close(f"{optimizer} bias", net.bias.data().asnumpy(),
                         b, 1e-5))
    return max(errs)


def phase_resnet_trainer(run):
    """DataParallelTrainer on a one-chip mesh: batch 128, bf16 via amp.init,
    SGD with momentum."""
    import jax
    import mxnet_tpu as mx
    from mxnet_tpu import amp, gluon
    from mxnet_tpu.parallel import make_mesh
    from mxnet_tpu.parallel.data_parallel import DataParallelTrainer
    ph = "2b resnet DataParallelTrainer"
    s = run.sizes
    err = _update_rule_reference(
        run, "sgd", {"learning_rate": 0.1, "momentum": 0.9})
    say(ph, f"sgd+momentum vs float64 numpy, 3 steps on Dense(6->4): "
            f"max abs err {err:.2e} (tolerance 1e-5)")
    err = _update_rule_reference(run, "adam", {"learning_rate": 0.01})
    say(ph, f"adam vs float64 numpy, 3 steps on Dense(6->4): "
            f"max abs err {err:.2e} (tolerance 1e-5)")

    # from here on the process computes conv/matmul in bf16 (amp.init is
    # global and one-way, as in the reference)
    amp.init(target_dtype="bfloat16")
    batch = s["resnet_batch"]
    mx.random.seed(SEED)
    net = _resnet(run)
    net.initialize(ctx=run.ctx)
    mesh = make_mesh({"dp": 1}, devices=jax.devices()[:1])
    trainer = DataParallelTrainer(
        net, gluon.loss.SoftmaxCrossEntropyLoss(), "sgd",
        {"learning_rate": 0.1, "momentum": 0.9}, mesh=mesh)
    x, y = _images(run, batch, np.random.RandomState(SEED))
    net(x)      # resolve deferred shapes (else the trainer's first step does)
    before = {n: p.data().asnumpy().copy()
              for n, p in net.collect_params().items()
              if p.grad_req != "null"}
    first, steady, losses = timed_steps(lambda: trainer.step(x, y),
                                        s["steady_steps"])
    for l in losses:
        assert_finite("loss", l)
    assert len(set(losses)) > 1, \
        f"the loss never moved on a fixed batch: {losses}"
    after = {n: net.collect_params()[n].data().asnumpy() for n in before}
    for n, a in after.items():
        assert_finite(n, a)
    still = [n for n in before if np.array_equal(before[n], after[n])]
    assert not still, f"parameters the training did not move: {still[:5]}"
    say(ph, f"model={type(net).__name__} batch={batch} bf16 sgd+momentum "
            f"first_call_s={first:.2f} steady_step_s={steady:.4f} "
            f"compile_s~={first - steady:.2f} "
            f"losses={[round(l, 4) for l in losses]}")
    memory_line(ph)


# ---------------------------------------------------------------------------
# phase 3: BERT-base training
# ---------------------------------------------------------------------------

def _bert_batch(run, batch):
    import mxnet_tpu as mx
    s = run.sizes
    rng = np.random.RandomState(SEED)
    vocab, seq = s["bert"]["vocab_size"], s["bert_seq"]
    return (mx.nd.array(rng.randint(0, vocab, (batch, seq)), dtype="int32"),
            mx.nd.zeros((batch, seq), dtype="int32"),
            mx.nd.array(rng.randint(0, 2, (batch,)), dtype="int32"))


def _bert_trainer(run, **trainer_args):
    import mxnet_tpu as mx
    from mxnet_tpu import gluon
    from mxnet_tpu.gluon.model_zoo.nlp.bert import get_bert_model
    from mxnet_tpu.parallel.data_parallel import DataParallelTrainer
    mx.random.seed(SEED)
    # dropout 0: the flash kernel has no attention dropout, so this is the
    # setting under which training takes it
    net = get_bert_model(dropout=0.0, use_flash=True, use_decoder=False,
                         **run.sizes["bert"])
    net.initialize(ctx=run.ctx)
    ce = gluon.loss.SoftmaxCrossEntropyLoss()
    # From a random start adam's first steps move every weight by the whole
    # rate whatever its gradient, and the loss of one fixed batch swings
    # (0.70, 3.43, 2.02, ... at 1e-4 on the chip).  At 1e-5 it still swings,
    # but one chip and N chips stay within a few bf16 steps of each other,
    # which is what phase 6 compares.  That adam itself is right is checked
    # against numpy in phase 2b; the loss here has to be finite and to move.
    trainer = DataParallelTrainer(
        net, lambda out, label: ce(out[-1], label), "adam",
        {"learning_rate": 1e-5}, **trainer_args)
    return net, trainer


def phase_bert(run):
    import jax
    from mxnet_tpu.parallel import make_mesh
    ph = "3 bert"
    s = run.sizes
    batch = s["bert_batch"]
    mesh = make_mesh({"dp": 1}, devices=jax.devices()[:1])
    net, trainer = _bert_trainer(run, mesh=mesh)
    data = _bert_batch(run, batch)
    first, steady, losses = timed_steps(lambda: trainer.step(*data),
                                        s["steady_steps"])
    for l in losses:
        assert_finite("loss", l)
    assert len(set(losses)) > 1, \
        f"the loss never moved on a fixed batch: {losses}"
    hlo = trainer.compiled_step_text(*data)
    calls = mosaic_calls(hlo, "mxtpu_flash_fwd")
    bwd_calls = mosaic_calls(hlo, "mxtpu_flash_bwd")
    if run.rehearsal:
        say(ph, "REHEARSAL: no Mosaic on the CPU, compiled step not checked")
    else:
        layers = s["bert"]["num_layers"]
        assert len(calls) >= layers and len(bwd_calls) >= layers, \
            f"{len(calls)} forward and {len(bwd_calls)} backward flash " \
            f"Mosaic calls in the compiled step, expected one of each for " \
            f"each of {layers} layers"
    run.record["bert_losses"] = losses
    say(ph, f"layers={s['bert']['num_layers']} units={s['bert']['units']} "
            f"batch={batch} seq={s['bert_seq']} bf16 adam "
            f"first_call_s={first:.2f} steady_step_s={steady:.4f} "
            f"compile_s~={first - steady:.2f} "
            f"flash_mosaic_calls_in_step={len(calls)}+{len(bwd_calls)} "
            f"losses={[round(l, 4) for l in losses]}")
    memory_line(ph)


# ---------------------------------------------------------------------------
# phase 4: every Pallas kernel against its XLA reference
# ---------------------------------------------------------------------------

def _compiled(run, kernel_name, fn, *args):
    """jit ``fn``, check the Mosaic call is in what was compiled, return
    (callable, compile seconds)."""
    import jax
    t0 = time.perf_counter()
    exe = jax.jit(fn).lower(*args).compile()
    dt = time.perf_counter() - t0
    if not run.rehearsal:
        assert mosaic_calls(exe.as_text(), kernel_name), \
            f"no Mosaic call of {kernel_name} in the compiled program"
    return exe, dt


def _kernels_flash(run):
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.ops import flash_attention
    ph = "4 kernels flash_attention"

    def naive_rows(q, k, v, causal):
        # plain XLA softmax(QK^T)V in float32 over the same bf16 inputs
        q, k, v = (a.astype(jnp.float32) for a in (q, k, v))
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / math.sqrt(q.shape[-1])
        if causal:
            L = s.shape[-1]
            s = jnp.where(jnp.tril(jnp.ones((L, L), bool)), s, -1e30)
        return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), v)

    def naive(q, k, v, causal):
        # 8 rows at a time where all the (L, L) scores at once would not
        # fit beside their gradients (64 rows at L = 4096: 4.3 GB a copy)
        bh, L = q.shape[1], q.shape[2]
        if bh * L * L * 4 < 2 ** 30 or bh % 8:
            return naive_rows(q, k, v, causal)

        def blocks(a):
            return a.reshape(bh // 8, 1, 8, L, a.shape[-1])
        out = jax.lax.map(lambda qkv: naive_rows(*qkv, causal),
                          (blocks(q), blocks(k), blocks(v)))
        return out.reshape(1, bh, L, v.shape[-1])

    for bh, L, d, dv, causals in run.sizes["flash_shapes"]:
        rng = np.random.RandomState(SEED)
        q, k = (jnp.asarray(rng.randn(1, bh, L, d), jnp.bfloat16)
                for _ in range(2))
        v, g = (jnp.asarray(rng.randn(1, bh, L, dv), jnp.bfloat16)
                for _ in range(2))
        for causal in causals:
            def loss(fn, q, k, v):
                out = fn(q, k, v).astype(jnp.float32)
                return jnp.sum(out * g.astype(jnp.float32)), out

            def run_one(fn):
                return lambda q, k, v: jax.value_and_grad(
                    lambda q, k, v: loss(fn, q, k, v), argnums=(0, 1, 2),
                    has_aux=True)(q, k, v)

            exe, dt = _compiled(
                run, "mxtpu_flash_fwd",
                run_one(lambda q, k, v: flash_attention(q, k, v,
                                                        causal=causal)),
                q, k, v)
            assert run.rehearsal or mosaic_calls(exe.as_text(),
                                                 "mxtpu_flash_bwd"), \
                "no Mosaic call of mxtpu_flash_bwd in the compiled gradient"
            (_, out), grads = exe(q, k, v)
            (_, ref), ref_grads = jax.jit(run_one(
                lambda q, k, v: naive(q, k, v, causal)))(q, k, v)
            # bf16 inputs and outputs, f32 accumulation: 2 bf16 ulps
            # (2**-7) of the largest reference value
            errs = [assert_close("flash out", out, ref, 2e-2)]
            errs += [assert_close(f"flash d{n}", a, b, 2e-2)
                     for n, a, b in zip("qkv", grads, ref_grads)]
            say(ph, f"(B*H,L,D,Dv)=({bh},{L},{d},{dv}) bf16 causal={causal} "
                    f"compile_s={dt:.2f} max_abs_err out,dq,dk,dv="
                    f"{[float(f'{e:.2e}') for e in errs]} "
                    f"(tolerance 2e-2 x scale)")


def _kernels_flash_backward(run):
    """``mxtpu_flash_bwd`` against the float32 scan it replaced
    (``_scan_backward``, what runs wherever there is no kernel), from the
    forward kernel's own residuals."""
    import importlib
    import jax
    import jax.numpy as jnp
    mod = importlib.import_module("mxnet_tpu.ops.flash_attention")
    ph = "4 kernels flash_attention backward"
    for bh, L, d, dv, causal in run.sizes["flash_bwd_shapes"]:
        rng = np.random.RandomState(SEED)
        q, k = (jnp.asarray(rng.randn(bh, L, d), jnp.bfloat16)
                for _ in range(2))
        v, do = (jnp.asarray(rng.randn(bh, L, dv), jnp.bfloat16)
                 for _ in range(2))
        kw = dict(causal=causal, sm_scale=d ** -0.5)
        blocks = dict(zip(("bq", "bk"), mod._use_pallas(L, L, d, dv)),
                      interpret=run.rehearsal)
        out, lse = jax.jit(lambda q, k, v: mod._pallas_forward(
            q, k, v, **kw, **blocks))(q, k, v)
        exe, dt = _compiled(
            run, "mxtpu_flash_bwd",
            lambda *a: mod._pallas_backward(*a, **kw, **blocks),
            q, k, v, out, lse, do)
        got = exe(q, k, v, out, lse, do)
        want = jax.jit(lambda *a: mod._scan_backward(
            *a, **kw, bk=mod._pick_block(L, 256)))(q, k, v, out, lse, do)
        # P and dS enter the MXU in bf16 where the scan keeps them float32:
        # 2 bf16 ulps of the largest reference value, as the forward's line
        errs = [assert_close(f"flash bwd d{n}", a, b, 2e-2)
                for n, a, b in zip("qkv", got, want)]
        t0 = time.perf_counter()
        for _ in range(10):
            got = exe(q, k, v, out, lse, do)
        jax.block_until_ready(got)
        say(ph, f"(B*H,L,D,Dv)=({bh},{L},{d},{dv}) bf16 causal={causal} "
                f"blocks={blocks['bq']}x{blocks['bk']} compile_s={dt:.2f} "
                f"bwd_ms={(time.perf_counter() - t0) * 100:.3f} "
                f"max_abs_err dq,dk,dv vs scan="
                f"{[float(f'{e:.2e}') for e in errs]} "
                f"(tolerance 2e-2 x scale)")


def _kernels_gmm(run):
    """``mxtpu_gmm`` and its two backward kernels against ``lax.ragged_dot``
    in float32 over the same bf16 operands: a buffer of which a part is
    routed, in groups of uneven size."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.ops.grouped_matmul import grouped_matmul
    ph = "4 kernels grouped_matmul"
    z = run.sizes["gmm"]
    rng = np.random.RandomState(SEED)
    sizes = rng.multinomial(z["routed"], np.ones(z["groups"]) / z["groups"])
    gs = jnp.asarray(sizes, jnp.int32)
    lhs = jnp.asarray(rng.randn(z["rows"], z["k"]), jnp.bfloat16)
    rhs = jnp.asarray(rng.randn(z["groups"], z["k"], z["n"]) * z["k"] ** -.5,
                      jnp.bfloat16)
    g = jnp.asarray(rng.randn(z["rows"], z["n"]), jnp.bfloat16)

    def both(fn):
        def loss(lhs, rhs):
            out = fn(lhs, rhs).astype(jnp.float32)
            return jnp.sum(out * g.astype(jnp.float32)), out
        return lambda lhs, rhs: jax.value_and_grad(
            loss, argnums=(0, 1), has_aux=True)(lhs, rhs)

    exe, dt = _compiled(run, "mxtpu_gmm",
                        both(lambda a, b: grouped_matmul(a, b, gs)), lhs, rhs)
    (_, out), grads = exe(lhs, rhs)
    # ragged_dot leaves the rows past the total unspecified on a TPU (the
    # v5e gave leftovers, NaN among them), so the reference masks them
    routed_rows = jnp.arange(z["rows"])[:, None] < int(sizes.sum())
    (_, ref), ref_grads = jax.jit(both(
        lambda a, b: jnp.where(routed_rows, jax.lax.ragged_dot(
            jnp.where(routed_rows, a, 0).astype(jnp.float32),
            b.astype(jnp.float32), gs,
            precision=jax.lax.Precision.HIGHEST), 0)))(lhs, rhs)
    # bf16 operands and results, f32 accumulation: 2 bf16 ulps of the
    # largest reference value, as for flash
    errs = [assert_close("gmm out", out, ref, 2e-2)]
    errs += [assert_close(f"gmm d{n}", a, b, 2e-2)
             for n, a, b in zip(("lhs", "rhs"), grads, ref_grads)]
    routed = int(sizes.sum())
    assert not np.asarray(out[routed:], np.float32).any(), \
        "rows past the last routed one are not zero"
    t0 = time.perf_counter()
    for _ in range(10):
        res = exe(lhs, rhs)
    jax.block_until_ready(res)
    say(ph, f"(rows,routed,groups,K,N)=({z['rows']},{routed},{z['groups']},"
            f"{z['k']},{z['n']}) bf16 group sizes {sizes.min()}..{sizes.max()} "
            f"compile_s={dt:.2f} fwd+bwd_ms={(time.perf_counter() - t0) * 100:.3f} "
            f"max_abs_err out,dlhs,drhs={[float(f'{e:.2e}') for e in errs]} "
            f"(tolerance 2e-2 x scale)")


def _expert_layer_buffers(run):
    """``dropless_moe_apply`` at three fills of its worst case — one buffer
    of a quarter of it twice, then two, counted on the device — against one
    buffer of the worst case on the same operands: output and the five
    gradients.  The same products (a buffer's size changes no tile of the
    kernels), sums in another order."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.parallel import moe
    ph = "4 kernels expert layer"
    z = run.sizes["moe"]
    tokens, k, held, d, h = (z[n] for n in ("tokens", "top_k", "held", "d",
                                            "h"))
    rng = np.random.RandomState(SEED)
    x, g = (jnp.asarray(rng.randn(tokens, d), jnp.bfloat16) for _ in range(2))
    weights = jnp.asarray(rng.rand(tokens, k) + 0.1, jnp.float32)
    stacks = [jnp.asarray(rng.randn(held, a, b) * a ** -.5, jnp.bfloat16)
              for a, b in ((d, h), (d, h), (h, d))]
    full = moe.buffer_rows(tokens, k, held)

    def both(layer):
        def loss(x, weights, *stacks, experts):
            out = layer(x, weights, *stacks, experts=experts)
            return jnp.sum(out.astype(jnp.float32)
                           * g.astype(jnp.float32)), out
        return lambda experts, *floats: jax.value_and_grad(
            loss, argnums=tuple(range(5)), has_aux=True)(
                *floats, experts=experts)

    def layer(x, weights, *stacks, experts):
        return moe.dropless_moe_apply(x, experts, weights, *stacks)

    def worst_case(x, weights, *stacks, experts):
        return moe._experts_at(full, 0, x, weights, *stacks,
                               moe._make_plan(experts, held, 0)
                               ).astype(x.dtype)

    t, j = np.meshgrid(np.arange(tokens), np.arange(k), indexing="ij")
    spread = rng.randint(0, z["experts"] - held, size=(tokens, 1))
    absent = held + (spread + j) % (z["experts"] - held)
    exe = top = None
    for routed in z["routed"]:
        # `routed` of the choices go to held experts, the first of each
        # token before the second of any
        rank = j * tokens + rng.permutation(tokens)[:, None]
        experts = jnp.asarray(np.where(rank < routed, (t + j) % held, absent),
                              jnp.int32)
        floats = (experts, x, weights, *stacks)
        if exe is None:
            exe, dt = _compiled(run, "mxtpu_gmm", both(layer), *floats)
            top = jax.jit(both(worst_case))
        (_, out), grads = exe(*floats)
        (_, want), want_grads = top(*floats)
        errs = [assert_close("expert layer out", out, want, 2e-2)]
        errs += [assert_close(f"expert layer d{n}", a, b, 2e-2)
                 for n, a, b in zip(("x", "weights", "gate", "up", "down"),
                                    grads, want_grads)]
        say(ph, f"(tokens,top_k,held,d,h)=({tokens},{k},{held},{d},{h}) "
                f"bf16 routed={routed} of {full} through "
                f"{moe.rung_rows(routed, tokens, k, held)} rows in buffers "
                f"of {moe.window_rows(tokens, k, held)} compile_s={dt:.2f} "
                f"fwd+bwd_ms={_clock_ms(exe, *floats):.3f} "
                f"worst_case_fwd+bwd_ms={_clock_ms(top, *floats):.3f} "
                f"max_abs_err out,dx,dweights,dgate,dup,ddown vs one buffer "
                f"of the worst case={[float(f'{e:.2e}') for e in errs]} "
                f"(tolerance 2e-2 x scale)")


def _kernels_layernorm(run):
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.ops import fused_layer_norm
    ph = "4 kernels fused_layer_norm"
    rows, d = run.sizes["ln_rows"], run.sizes["ln_dim"]
    rng = np.random.RandomState(SEED)

    def reference(x, gamma, beta, res):
        h = x.astype(jnp.float32)
        if res is not None:
            h = h + res.astype(jnp.float32)
        mean = jnp.mean(h, -1, keepdims=True)
        var = jnp.mean(jnp.square(h - mean), -1, keepdims=True)
        y = (h - mean) * jax.lax.rsqrt(var + 1e-5) * gamma + beta
        return y.astype(x.dtype)

    for dtype, tol in ((jnp.float32, 1e-4), (jnp.bfloat16, 2e-2)):
        x, res, g = (jnp.asarray(rng.randn(rows, d), dtype)
                     for _ in range(3))
        gamma = jnp.asarray(1 + 0.1 * rng.randn(d), jnp.float32)
        beta = jnp.asarray(0.1 * rng.randn(d), jnp.float32)
        for with_res in (False, True):
            def both(fn):
                def f(x, gamma, beta, res):
                    def loss(x, gamma, beta, res):
                        y = fn(x, gamma, beta, res if with_res else None)
                        return jnp.sum(y.astype(jnp.float32)
                                       * g.astype(jnp.float32)), y
                    return jax.value_and_grad(
                        loss, argnums=(0, 1, 2), has_aux=True)(
                            x, gamma, beta, res)
                return f
            exe, dt = _compiled(
                run, "mxtpu_fused_ln_fwd",
                both(lambda x, gamma, beta, res: fused_layer_norm(
                    x, gamma, beta, residual=res)), x, gamma, beta, res)
            if not run.rehearsal:
                assert mosaic_calls(exe.as_text(), "mxtpu_fused_ln_bwd")
            (_, y), grads = exe(x, gamma, beta, res)
            (_, ry), rgrads = jax.jit(both(reference))(x, gamma, beta, res)
            # dgamma/dbeta sum `rows` terms: their tolerance is relative
            # to the reference's own largest value (assert_close scales)
            errs = [assert_close("ln y", y, ry, tol)]
            errs += [assert_close(f"ln d{n}", a, b, tol)
                     for n, a, b in zip(("x", "gamma", "beta"),
                                        grads, rgrads)]
            say(ph, f"rows={rows} D={d} {jnp.dtype(dtype).name} "
                    f"residual={with_res} compile_s={dt:.2f} max_abs_err "
                    f"y,dx,dgamma,dbeta={[float(f'{e:.2e}') for e in errs]}"
                    f" (tolerance {tol:g} x scale)")


def _kernels_bucket_update(run):
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.ops import fused_bucket_rule
    from mxnet_tpu.optimizer.optimizer import fused_rule
    from mxnet_tpu.parallel.zero import BucketPlan
    ph = "4 kernels fused_bucket_rule"
    # one bucket holding a whole ResNet-50, padded the way the ZeRO-1 plan
    # pads it for the chips that are here
    n = BucketPlan([(run.sizes["bucket_elems"],)], len(jax.devices()),
                   bound_bytes=1 << 40).lengths[0]
    rng = np.random.RandomState(SEED)
    p = jnp.asarray(rng.randn(n), jnp.float32)
    g = jnp.asarray(0.01 * rng.randn(n), jnp.float32)
    for name, hyper, kernel in (("sgd", {"momentum": 0.9},
                                 "mxtpu_bucket_sgd"),
                                ("adam", {}, "mxtpu_bucket_adam")):
        init, apply = fused_bucket_rule(name, **hyper)
        _, ref_apply = fused_rule(name, **hyper)

        def two_steps(fn):
            def f(p, g, s):
                for _ in range(2):      # the second step reads real state
                    p, s = fn(p, g, s, jnp.float32(0.1), jnp.float32(1e-4))
                return p, s
            return f
        s0 = init(p)
        exe, dt = _compiled(run, kernel, two_steps(apply), p, g, s0)
        new_p, new_s = exe(p, g, s0)
        ref_p, ref_s = jax.jit(two_steps(ref_apply))(p, g, s0)
        # same float32 arithmetic, element by element; only the order of
        # fused multiply-adds may differ
        errs = [assert_close(f"{name} p", new_p, ref_p, 1e-5)]
        for k in sorted(ref_s):
            errs.append(assert_close(f"{name} state[{k}]", new_s[k],
                                     ref_s[k], 1e-5))
        assert float(jnp.max(jnp.abs(new_p - p))) > 0
        say(ph, f"{name} n={n} f32 compile_s={dt:.2f} max_abs_err "
                f"p,{','.join(sorted(ref_s))}="
                f"{[float(f'{e:.2e}') for e in errs]} "
                f"(tolerance 1e-5 x scale)")


def _kernels_paged(run):
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.ops import paged_decode_attention
    ph = "4 kernels paged_decode_attention"
    z = run.sizes["paged"]
    B, H, KVH, D, BS = (z["batch"], z["heads"], z["kv_heads"],
                        z["head_dim"], z["block"])
    nbl = z["context"] // BS
    num_blocks = 1 + B * nbl
    rng = np.random.RandomState(SEED)
    # every sequence owns its own blocks, in a shuffled physical order;
    # lengths differ so that some table tails point at the null block
    tables = 1 + rng.permutation(B * nbl).reshape(B, nbl).astype(np.int32)
    pos = rng.randint(0, z["context"], (B,)).astype(np.int32)
    pos[0] = z["context"] - 1
    pos[-1] = 0
    for b in range(B):
        tables[b, pos[b] // BS + 1:] = 0
    q = jnp.asarray(rng.randn(B, H, D), jnp.float32)
    scale = 1.0 / math.sqrt(D)

    def reference(q, k_pool, v_pool, tables, pos):
        # dense gather through the block table, float32 softmax
        k = k_pool[tables].reshape(B, nbl * BS, KVH, D).astype(jnp.float32)
        v = v_pool[tables].reshape(B, nbl * BS, KVH, D).astype(jnp.float32)
        k = jnp.repeat(k, H // KVH, axis=2)
        v = jnp.repeat(v, H // KVH, axis=2)
        s = jnp.einsum("bhd,bthd->bht", q, k) * scale
        valid = jnp.arange(nbl * BS)[None, None, :] <= pos[:, None, None]
        p = jax.nn.softmax(jnp.where(valid, s, -1e30), -1)
        return jnp.einsum("bht,bthd->bhd", p, v).reshape(B, H * D)

    # the kernel contracts on the MXU at default precision (operands
    # rounded to bf16, float32 accumulation: 2**-8 of a score of size ~3
    # before the softmax); the reference is float32 throughout
    for dtype, tol in ((jnp.float32, 1e-2), (jnp.bfloat16, 1e-2)):
        k_pool = jnp.asarray(rng.randn(num_blocks, BS, KVH, D), dtype)
        v_pool = jnp.asarray(rng.randn(num_blocks, BS, KVH, D), dtype)
        exe, dt = _compiled(
            run, "mxtpu_paged_decode",
            lambda q, k, v, t, p: paged_decode_attention(q, k, v, t, p,
                                                         scale),
            q, k_pool, v_pool, tables, pos)
        out = exe(q, k_pool, v_pool, tables, pos)
        with jax.default_matmul_precision("highest"):
            ref = jax.jit(reference)(q, k_pool, v_pool, tables, pos)
        err = assert_close("paged attention", out, ref, tol)
        say(ph, f"B={B} heads={H}/{KVH} head_dim={D} block={BS} "
                f"context={z['context']} pool={jnp.dtype(dtype).name} "
                f"compile_s={dt:.2f} max_abs_err={err:.2e} "
                f"(tolerance {tol:g} x scale)")


def _clock_ms(exe, *args):
    """Mean milliseconds of five calls of a compiled program, by the host
    clock around ``block_until_ready``."""
    import jax
    t0 = time.perf_counter()
    for _ in range(5):
        out = exe(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) * 200


def _kernels_sparse(run):
    """The four kernels of ``ops/sparse_attention.py`` at the keye cell's
    shape, each against its blocked XLA form: the selection (the share of
    (query, key) pairs on which the two masks differ: their products sum in
    different orders, so a score at the threshold may fall either side),
    the masked flash forward and backward on the kernel's own mask against
    the scans, the alignment loss's value kernel against the XLA form and
    its gradient kernel against ``jax.grad`` of that."""
    import importlib
    import jax
    import jax.numpy as jnp
    sa = importlib.import_module("mxnet_tpu.ops.sparse_attention")
    fa = importlib.import_module("mxnet_tpu.ops.flash_attention")
    ph = "4 kernels sparse_attention"
    z = run.sizes["dsa"]
    h, hkv, seq, d = z["heads"], z["kv_heads"], z["seq"], z["d"]
    hi, di, topk, rows = z["index_heads"], z["index_dim"], z["topk"], \
        z["check_rows"]
    rng = np.random.RandomState(SEED)

    def draw(*shape, dtype=jnp.bfloat16):
        return jnp.asarray(rng.randn(*shape), dtype)
    q, k, v = draw(1, h, seq, d), draw(1, hkv, seq, d), draw(1, hkv, seq, d)
    qi, ki = draw(1, hi, seq, di), draw(1, seq, di)
    w = draw(1, seq, hi, dtype=jnp.float32)
    scale, sm = hi ** -0.5 * di ** -0.5, d ** -0.5
    mode = dict(interpret=run.rehearsal)

    # 1. the selection
    swapped = (jnp.swapaxes(qi, 2, 3), jnp.swapaxes(ki, 1, 2),
               jnp.swapaxes(w, 1, 2))
    exe, dt = _compiled(run, "mxtpu_dsa_index_select",
                        lambda *a: sa._pallas_index_select(
                            *a, topk, scale, **mode), *swapped)
    mask, tau, lse_i = exe(*swapped)
    want_mask, want_tau, want_lse = jax.jit(jax.vmap(
        lambda *a: sa._xla_index_select(*a, topk, scale)))(qi, ki, w)
    kept = float(jnp.sum(mask != 0))
    differ = float(jnp.sum((mask != 0) != (want_mask != 0))) / kept
    assert kept >= seq * min(topk, seq) * 0.9 and differ < 1e-2, \
        (kept, differ)
    assert_close("dsa tau", tau, want_tau, 1e-2)
    assert_close("dsa lse_i", lse_i, want_lse, 1e-2)
    say(ph, f"select (L,HI,dI,topk)=({seq},{hi},{di},{topk}) "
            f"compile_s={dt:.2f} ms={_clock_ms(exe, *swapped):.3f} "
            f"kept_share={kept / (seq * (seq + 1) / 2):.4f} "
            f"pairs_that_differ_from_the_xla_form={differ:.2e}")

    # 2. attention over the kernel's own mask, forward and backward
    flat = (h, seq, d)
    qr, kr, vr = (a.reshape(flat) for a in (
        q, jnp.repeat(k, h // hkv, 1), jnp.repeat(v, h // hkv, 1)))
    do = draw(*flat)
    blocks = dict(zip(("bq", "bk"), fa._use_pallas(seq, seq, d, d)), **mode)
    kw = dict(causal=True, sm_scale=sm)
    fwd, dt = _compiled(run, "mxtpu_dsa_attn_fwd",
                        lambda q, k, v, m: fa._pallas_forward(
                            q, k, v, mask=m, **kw, **blocks), qr, kr, vr, mask)
    out, lse = fwd(qr, kr, vr, mask)
    bwd, dt_b = _compiled(run, "mxtpu_dsa_attn_bwd",
                          lambda *a: fa._pallas_backward(
                              *a[:6], mask=a[6], **kw, **blocks),
                          qr, kr, vr, out, lse, do, mask)
    grads = bwd(qr, kr, vr, out, lse, do, mask)
    some = (qr[:rows], kr[:rows], vr[:rows])
    bk = fa._pick_block(seq, 256)
    want_out, want_lse = jax.jit(lambda *a: fa._scan_forward(
        *a[:3], **kw, bk=bk, mask=a[3]))(*some, mask)
    errs = [assert_close("dsa out", out[:rows], want_out, 2e-2),
            assert_close("dsa lse", lse[:rows], want_lse, 2e-2)]
    # dK / dV of a row sum over every query of that row: comparable row by
    # row, like dQ
    want = jax.jit(lambda *a: fa._scan_backward(
        *a[:6], **kw, bk=bk, mask=a[6]))(
            *some, out[:rows], lse[:rows], do[:rows], mask)
    errs += [assert_close(f"dsa d{n}", a[:rows], b, 2e-2)
             for n, a, b in zip("qkv", grads, want)]
    say(ph, f"attention (rows,L,D)={flat} bf16 blocks="
            f"{blocks['bq']}x{blocks['bk']} compile_s={dt:.2f}+{dt_b:.2f} "
            f"fwd_ms={_clock_ms(fwd, qr, kr, vr, mask):.3f} "
            f"bwd_ms={_clock_ms(bwd, qr, kr, vr, out, lse, do, mask):.3f} "
            f"max_abs_err out,lse,dq,dk,dv vs scans on {rows} rows="
            f"{[float(f'{e:.2e}') for e in errs]} (tolerance 2e-2 x scale)")

    # 3. the alignment loss: the value kernel and the gradient kernel, each
    # alone
    lse3 = lse.reshape(1, h, seq)
    args = (jnp.swapaxes(q, 2, 3), jnp.swapaxes(k, 2, 3), lse3, *swapped,
            mask, lse_i)
    value, dt = _compiled(run, "mxtpu_dsa_align_loss",
                          lambda *a: sa._pallas_index_loss(
                              *a, sm, scale, **mode), *args)
    grad, dt_g = _compiled(run, "mxtpu_dsa_align_loss_grad",
                           lambda *a: sa._pallas_index_loss_grad(
                               *a, sm, scale, **mode), *args)
    kl = value(*args)
    dqi, dki, dw = grad(*args)
    want_kl, want_g = jax.jit(jax.value_and_grad(
        lambda qi, ki, w: sa._xla_index_loss(
            q[0], jnp.repeat(k, h // hkv, 1)[0], lse3[0], qi, ki, w, mask[0],
            sm, scale), argnums=(0, 1, 2)))(qi[0], ki[0], w[0])
    errs = [assert_close("dsa kl", jnp.sum(kl), want_kl, 2e-2)]
    errs += [assert_close(f"dsa d{n}", a[0], b, 3e-2 * float(
        jnp.max(jnp.abs(b.astype(jnp.float32)))))
        for n, a, b in zip(("qi", "ki", "w"), (
            jnp.swapaxes(dqi, 2, 3), jnp.swapaxes(dki, 1, 2),
            jnp.swapaxes(dw, 1, 2)), want_g)]
    say(ph, f"align_loss compile_s={dt:.2f}+{dt_g:.2f} "
            f"value_ms={_clock_ms(value, *args):.3f} "
            f"grad_ms={_clock_ms(grad, *args):.3f} "
            f"mean_kl={float(jnp.sum(kl)) / seq:.4e} "
            f"max_abs_err kl vs the xla form, dqi,dki,dw vs jax.grad of it="
            f"{[float(f'{e:.2e}') for e in errs]}")


def _kernels_kda(run):
    """The scan of ``ops/linear_attention.py`` at the kimi cell's shape, the
    forward kernel and the backward kernel each alone, against the XLA form
    of the same chunked mathematics on some of the heads."""
    import importlib
    import jax
    import jax.numpy as jnp
    la = importlib.import_module("mxnet_tpu.ops.linear_attention")
    ph = "4 kernels linear_attention"
    z = run.sizes["kda"]
    h, seq, d, some = z["heads"], z["seq"], z["d"], z["check_heads"]
    rng = np.random.RandomState(SEED)
    size, mode = la.chunk_size(seq), dict(interpret=run.rehearsal)

    beta = 1 / (1 + np.exp(-rng.randn(1, seq, h)))
    q, k, v = (rng.randn(1, seq, h, d) for _ in range(3))
    # the configuration's draws: rate uniform on [1, 16] a head, dt
    # log-uniform on [1e-3, 1e-1] a channel
    g = -rng.uniform(1, 16, (1, 1, h, 1)) \
        * np.exp(rng.uniform(np.log(1e-3), np.log(1e-1), (1, seq, h, d)))

    def flat(a, dtype=jnp.bfloat16, heads=h):
        return jnp.asarray(a[:, :, :heads].reshape(1, seq, -1), dtype)

    def operands(heads):
        return (flat(q, heads=heads), flat(k, heads=heads),
                flat(v, heads=heads), flat(g, jnp.float32, heads),
                flat(beta, jnp.float32, heads))
    zeros = jnp.zeros((1, h, d, d), jnp.float32)
    do = flat(rng.randn(1, seq, h, d))

    every = operands(h)
    fwd, dt = _compiled(run, "mxtpu_kda_fwd",
                        lambda *a: la._pallas_forward(*a, h, size, **mode),
                        *every)
    out, states, final = fwd(*every)
    bwd, dt_b = _compiled(run, "mxtpu_kda_bwd",
                          lambda *a: la._pallas_backward(*a, size, **mode),
                          *every, states, do, zeros)
    grads = bwd(*every, states, do, zeros)

    few = operands(some)
    want_out, want_states, want_final = jax.jit(
        lambda *a: la._xla_forward(*a, some, size))(*few)
    want = jax.jit(lambda *a: la._xla_backward(*a, size))(
        *few, want_states, do[..., :some * d], zeros[:, :some])
    errs = [assert_close("kda o", out[..., :some * d], want_out, 2e-2),
            assert_close("kda final state", final[:, :some], want_final,
                         2e-2)]
    errs += [assert_close(f"kda d{n}", a[..., :some * w], b, 3e-2 * float(
        jnp.max(jnp.abs(b.astype(jnp.float32)))))
        for n, a, b, w in zip(("q", "k", "v", "g", "beta"), grads, want,
                              (d, d, d, d, 1))]
    say(ph, f"scan (L,H,dk,dv,chunk)=({seq},{h},{d},{d},{size}) bf16 "
            f"compile_s={dt:.2f}+{dt_b:.2f} "
            f"fwd_ms={_clock_ms(fwd, *every):.3f} "
            f"bwd_ms={_clock_ms(bwd, *every, states, do, zeros):.3f} "
            f"max_abs_err o,state,dq,dk,dv,dg,dbeta vs the xla form on "
            f"{some} heads={[float(f'{e:.2e}') for e in errs]}")


def phase_kernels(run):
    _kernels_flash(run)
    _kernels_flash_backward(run)
    _kernels_sparse(run)
    _kernels_kda(run)
    _kernels_gmm(run)
    _expert_layer_buffers(run)
    _kernels_layernorm(run)
    _kernels_bucket_update(run)
    _kernels_paged(run)
    memory_line("4 kernels")


# ---------------------------------------------------------------------------
# phase 5: serving
# ---------------------------------------------------------------------------

def phase_serve(run):
    import mxnet_tpu as mx
    from mxnet_tpu.gluon.model_zoo.nlp.llama import llama3_8b
    from mxnet_tpu.serving import ContinuousBatcher, InferenceEngine, Request
    ph = "5 serve"
    s = run.sizes
    mx.random.seed(SEED)
    net = llama3_8b(**s["llama"])
    net.initialize(ctx=run.ctx)
    cfg = net.cfg
    net(mx.nd.array(np.zeros((1, 4), np.int32)))     # materialize shapes
    net.hybridize()
    t0 = time.perf_counter()
    engine = InferenceEngine(net, **s["serve"])
    engine.warmup()
    warm = time.perf_counter() - t0
    compiles = engine.stats["compiles"]

    # the engine against the model's own forward, on one short prompt:
    # prefill logits, then one decode step through the paged cache
    rng = np.random.RandomState(SEED)
    prompt = rng.randint(0, cfg.vocab_size, (9,)).tolist()

    def forward_logits(tokens):
        out = net(mx.nd.array(np.asarray([tokens], np.int32)))
        return out.asnumpy()[0, -1].astype(np.float32)

    # the engine computes in float32; the model's own forward runs under
    # amp by now, its matmuls in bf16 (before amp.init the two are bitwise
    # equal on the chip): the tolerance is bf16's, 5% of the largest logit
    tok, logits = engine.prefill(0, prompt)
    want = forward_logits(prompt)
    e1 = assert_close("prefill logits vs forward", np.asarray(logits),
                      want, 5e-2)
    assert engine.reserve(0, len(prompt))
    _, lg = engine.decode([(0, int(tok), len(prompt))])
    e2 = assert_close("decode logits vs forward", np.asarray(lg[0]),
                      forward_logits(prompt + [int(tok)]), 5e-2)
    engine.release(0)
    say(ph, f"engine vs net.forward on a 9-token prompt: max_abs_err "
            f"prefill={e1:.2e} decode={e2:.2e} (tolerance 5e-2 x largest "
            f"logit {float(np.max(np.abs(want))):.2f})")

    batcher = ContinuousBatcher(engine)
    reqs = [Request(rng.randint(0, cfg.vocab_size, (n,)).tolist(), budget)
            for n, budget in zip(s["serve_prompts"], s["serve_budgets"])]
    for r in reqs:
        batcher.submit(r)
    t0 = time.perf_counter()
    stats = batcher.run()
    wall = time.perf_counter() - t0
    assert len(batcher.finished) == len(reqs), \
        f"{len(batcher.finished)} of {len(reqs)} requests finished"
    for r, budget in zip(reqs, s["serve_budgets"]):
        assert r.finish_reason == "length", (r.id, r.finish_reason)
        assert len(r.generated) == budget, (r.id, len(r.generated), budget)
        assert all(0 <= t < cfg.vocab_size for t in r.generated), r.generated
    assert engine.stats["compiles_after_warmup"] == 0, engine.stats
    assert engine.cache.blocks_in_use == 0, engine.cache.stats()
    engine.cache.check_leaks()
    say(ph, f"llama hidden={cfg.hidden_size} heads={cfg.num_heads}/"
            f"{cfg.num_kv_heads} ffn={cfg.intermediate_size} "
            f"vocab={cfg.vocab_size} layers={cfg.num_layers} fp32 "
            f"{s['serve']} warmup_s={warm:.2f} graphs_compiled={compiles} "
            f"requests={len(reqs)} tokens={stats['tokens_generated']} "
            f"decode_steps={stats['decode_steps']} run_s={wall:.3f} "
            f"compiles_after_warmup=0 blocks_in_use=0 "
            f"first_tokens={[r.generated[0] for r in reqs]}")
    memory_line(ph)


# ---------------------------------------------------------------------------
# phase 6: the same trainers over every visible chip
# ---------------------------------------------------------------------------

def _assert_on_every_chip(name, arrays, devices):
    for a in arrays:
        assert a.sharding.device_set == set(devices), \
            f"{name}: lives on {a.sharding.device_set}, not on all chips"


def phase_all_chips(run):
    import jax
    import mxnet_tpu as mx
    from mxnet_tpu import gluon
    from mxnet_tpu.parallel.data_parallel import DataParallelTrainer
    ph = "6 all chips"
    s = run.sizes
    devices = jax.devices()
    n = len(devices)

    # ResNet, no mesh argument: the default is dp over every chip
    mx.random.seed(SEED)
    net = _resnet(run)
    net.initialize(ctx=run.ctx)
    trainer = DataParallelTrainer(
        net, gluon.loss.SoftmaxCrossEntropyLoss(), "sgd",
        {"learning_rate": 0.1, "momentum": 0.9})
    assert dict(trainer.mesh.shape) == {"dp": n}, trainer.mesh.shape
    x, y = _images(run, s["resnet_batch"] * n, np.random.RandomState(SEED))
    first, steady, losses = timed_steps(lambda: trainer.step(x, y),
                                        s["steady_steps"])
    for l in losses:
        assert_finite("loss", l)
    assert len(set(losses)) > 1, losses
    _assert_on_every_chip(
        "resnet params",
        [p.data().data for p in net.collect_params().values()], devices)
    say(ph, f"resnet dp={n} batch={s['resnet_batch']}x{n} "
            f"first_call_s={first:.2f} steady_step_s={steady:.4f} "
            f"losses={[round(l, 4) for l in losses]}")
    del net, trainer, x, y
    release()

    # BERT at the one-chip global batch: the same losses as phase 3
    net, trainer = _bert_trainer(run)
    data = _bert_batch(run, s["bert_batch"])
    _, _, losses = timed_steps(lambda: trainer.step(*data), 2)
    want = run.record["bert_losses"][:3]
    # same seed, same batch; the gradient mean is taken over dp shards in
    # another order, and adam's m/sqrt(v) turns a last-bit difference in a
    # near-zero gradient into a full-size step, so the three losses agree
    # to a few bf16 steps of a value near 0.7, not bitwise
    err = assert_close("bert dp loss vs one chip", losses, want, 5e-2)
    say(ph, f"bert dp={n} global batch={s['bert_batch']} "
            f"losses={[round(l, 4) for l in losses]} "
            f"one-chip={[round(l, 4) for l in want]} "
            f"max_abs_diff={err:.2e} (tolerance 5e-2)")
    del net, trainer
    release()

    # BERT at the per-chip batch, replicated update then ZeRO-1
    data = _bert_batch(run, s["bert_batch"] * n)
    for shard in (False, True):
        net, trainer = _bert_trainer(run, shard_updates=shard)
        first, steady, losses = timed_steps(lambda: trainer.step(*data),
                                            s["steady_steps"])
        for l in losses:
            assert_finite("loss", l)
        assert len(set(losses)) > 1, losses
        _assert_on_every_chip(
            "bert params",
            [p.data().data for p in net.collect_params().values()], devices)
        # where the optimizer state really lives is not in any public
        # report (comm_stats computes it from shapes): read the shards
        leaves = [l for l in jax.tree.leaves(trainer._opt_state)
                  if l.ndim >= 1]
        per_chip = sum(l.addressable_shards[0].data.size for l in leaves)
        total = sum(l.size for l in leaves)
        assert trainer.comm_stats()["zero1"] == shard
        hlo = trainer.compiled_step_text(*data)
        flash = len(mosaic_calls(hlo, "mxtpu_flash_fwd"))
        flash_bwd = len(mosaic_calls(hlo, "mxtpu_flash_bwd"))
        in_step = len(mosaic_calls(hlo, "mxtpu_bucket_adam"))
        if not run.rehearsal:
            # XLA partitions the replicated step itself and cannot
            # partition a Mosaic call: the kernels are there because the op
            # wrapped each in a shard_map (ops/flash_attention.py)
            assert flash >= s["bert"]["num_layers"], flash
            assert flash_bwd >= s["bert"]["num_layers"], flash_bwd
        if shard:
            # every vector leaf is a bucket cut in n equal shards
            assert per_chip * n == total, (per_chip, n, total)
            if not run.rehearsal:
                assert in_step >= 1, "no fused-update Mosaic call in the " \
                                     "compiled ZeRO-1 step"
        else:
            assert per_chip == total, (per_chip, total)
            assert in_step == 0, in_step
        used = [(d.memory_stats() or {}).get("bytes_in_use") for d in devices]
        if not run.rehearsal:
            assert all(u and u > 0 for u in used), used
        say(ph, f"bert dp={n} batch={s['bert_batch']}x{n} "
                f"shard_updates={shard} first_call_s={first:.2f} "
                f"steady_step_s={steady:.4f} "
                f"opt_state_elems_per_chip={per_chip} of {total} "
                f"flash_mosaic_calls_in_step={flash}+{flash_bwd} "
                f"bucket_update_mosaic_calls_in_step={in_step} "
                f"bytes_in_use={used} "
                f"losses={[round(l, 4) for l in losses]}")
        del net, trainer
        release()
    memory_line(ph)


# ---------------------------------------------------------------------------

class Run:
    def __init__(self, rehearsal):
        import jax
        import mxnet_tpu as mx
        self.rehearsal = rehearsal
        self.sizes = SIZES["rehearsal" if rehearsal else "chip"]
        self.platform = "cpu" if rehearsal else "tpu"
        self.ctx = mx.cpu(0) if rehearsal else mx.tpu(0)
        self.record = {}
        self.n_devices = len(jax.devices())


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--rehearse-on-cpu", action="store_true",
                    help="toy sizes on the CPU backend, kernels in the "
                         "Pallas interpreter: a rehearsal, not a result")
    args = ap.parse_args(argv)

    import jax
    dev = jax.devices()[0]
    print(f"platform: {dev.platform}", flush=True)
    print(f"device_kind: {dev.device_kind}", flush=True)
    print(f"device_count: {len(jax.devices())}", flush=True)
    if args.rehearse_on_cpu:
        if dev.platform != "cpu":
            print("chip_smoke: --rehearse-on-cpu is for the CPU backend; "
                  "run it under JAX_PLATFORMS=cpu", file=sys.stderr)
            return 2
        print("REHEARSAL on the CPU backend: toy sizes, Pallas kernels "
              "interpreted; nothing below is a measurement", flush=True)
    elif dev.platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (first device is "
              f"{dev.platform!r}); this run proves nothing and stops here",
              file=sys.stderr)
        return 1

    from mxnet_tpu import runtime
    from mxnet_tpu.ops.kernel_mode import interpret_kernels
    print(f"compile cache: {runtime.enable_compile_cache()}", flush=True)

    t_start = time.perf_counter()
    run = Run(args.rehearse_on_cpu)
    phases = [phase_context, phase_resnet_readme, phase_resnet_trainer,
              phase_bert, phase_kernels, phase_serve]
    if run.n_devices > 1:
        phases.append(phase_all_chips)
    with interpret_kernels() if run.rehearsal else contextlib.nullcontext():
        for phase in phases:
            t0 = time.perf_counter()
            phase(run)
            release()
            say(phase.__name__, f"done in {time.perf_counter() - t0:.1f}s")
    print(f"all {len(phases)} phases passed in "
          f"{time.perf_counter() - t_start:.1f}s", flush=True)
    result = {"ok": True,
              "device": {"platform": dev.platform, "kind": dev.device_kind,
                         "count": len(jax.devices())}}
    if run.rehearsal:
        result["rehearsal"] = True
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
