"""On-chip measurement of the three memory levers (VERDICT r4 weak #4).

Each config runs in ITS OWN child process (MXTPU_EXP_CHILD), so
``device.memory_stats()['peak_bytes_in_use']`` isolates that config's
peak HBM.  One JSON line per config on stdout.  A chip belongs to one
process at a time: the parent never touches JAX, and the children run
one after another.

Levers (all correctness-proven on the virtual mesh in tests/):
  accum_*   — in-graph gradient accumulation (lax.scan microbatching,
              DataParallelTrainer.step_accum) vs the one-shot big batch:
              peak HBM should fall with n_micro, wall-clock/sample cost
              is the price.  Reference analog: example/image-class
              gradient accumulation for >GPU-memory batches.
  ce_*      — blocked fused linear+CE (ops/blocked_cross_entropy.py,
              never materializes the (N, V) logits) vs the naive
              materialized path at V in {32k, 128k} + an N*V size where
              naive OOMs a 16 GB chip and fused must survive.
  zero1     — single-chip report: measured param/adam-state HBM plus the
              analytic 1/N split ZeRO-1 gives at 8/256 chips.  The
              on/off STEP-TIME delta needs dp>1 and real wire — not
              measurable on one chip (shard_updates is a no-op at dp=1);
              correctness is covered by the multichip dryrun oracle.
"""
from __future__ import annotations

import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
sys.path.insert(0, REPO)

MATRIX = {
    "accum_base": dict(kind="accum", n_micro=1),
    "accum_4": dict(kind="accum", n_micro=4),
    "accum_8": dict(kind="accum", n_micro=8),
    "ce_naive_32k": dict(kind="ce", impl="naive", vocab=32768,
                         tokens=8192),
    "ce_fused_32k": dict(kind="ce", impl="fused", vocab=32768,
                         tokens=8192),
    "ce_naive_128k": dict(kind="ce", impl="naive", vocab=131072,
                          tokens=8192),
    "ce_fused_128k": dict(kind="ce", impl="fused", vocab=131072,
                          tokens=8192),
    # 32768 tokens x 131072 vocab: logits alone = 16 GB fp32 — past the
    # v5e's HBM. naive must OOM (that IS the datum); fused must survive.
    "ce_naive_oom32k": dict(kind="ce", impl="naive", vocab=131072,
                            tokens=32768, expect_oom=True),
    "ce_fused_32ktok": dict(kind="ce", impl="fused", vocab=131072,
                            tokens=32768),
    "zero1": dict(kind="zero1"),
}


def _peak_mb():
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    return round(peak / 1e6, 1) if peak is not None else None


def _platform():
    import jax
    return jax.devices()[0].platform


def _run_accum(n_micro):
    """ResNet-18, global batch 256 via one shot (n_micro=1) or scan
    microbatching: samples/s + peak HBM."""
    import numpy as np
    import mxnet_tpu as mx
    from mxnet_tpu import gluon
    from mxnet_tpu.gluon.model_zoo.vision import resnet18_v1
    from mxnet_tpu.parallel import make_mesh
    from mxnet_tpu.parallel.data_parallel import DataParallelTrainer
    import jax

    batch = int(os.environ.get("MXTPU_LEVER_BATCH", "256"))
    size = int(os.environ.get("MXTPU_LEVER_IMG", "128"))
    iters = int(os.environ.get("MXTPU_LEVER_ITERS", "10"))
    if _platform() == "cpu":   # smoke scale
        batch, size, iters = 32, 64, 2

    net = resnet18_v1()
    net.initialize()
    mesh = make_mesh({"dp": 1}, devices=jax.devices()[:1])
    tr = DataParallelTrainer(net, gluon.loss.SoftmaxCrossEntropyLoss(),
                             "adam", {"learning_rate": 1e-3}, mesh=mesh)
    data = mx.nd.array(np.random.RandomState(0).rand(
        batch, 3, size, size).astype(np.float32))
    label = mx.nd.zeros((batch,))

    def one_step():
        if n_micro == 1:
            return tr.step(data, label)
        return tr.step_accum(data, label, n_micro=n_micro)

    loss = one_step()           # compile + warmup
    loss.asnumpy()
    t0 = time.perf_counter()
    for _ in range(iters):
        loss = one_step()
    loss.asnumpy()
    dt = (time.perf_counter() - t0) / iters
    return {"samples_per_sec": round(batch / dt, 1),
            "ms_per_step": round(dt * 1e3, 2),
            "batch": batch, "img": size, "peak_hbm_mb": _peak_mb()}


def _run_ce(impl, vocab, tokens, expect_oom=False):
    """Fused blocked CE vs naive materialized logits: fwd+bwd of the
    mean loss over a (tokens, d) x (d, vocab) head."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.ops.blocked_cross_entropy import \
        fused_linear_cross_entropy

    d = int(os.environ.get("MXTPU_LEVER_D", "1024"))
    iters = int(os.environ.get("MXTPU_LEVER_ITERS", "10"))
    if _platform() == "cpu":   # smoke scale
        tokens, vocab, d, iters = min(tokens, 512), min(vocab, 2048), \
            256, 2
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (tokens, d), jnp.bfloat16)
    w = jax.random.normal(key, (d, vocab), jnp.bfloat16) * 0.02
    t = jax.random.randint(key, (tokens,), 0, vocab)

    if impl == "fused":
        def loss_fn(x, w):
            return fused_linear_cross_entropy(x, w, t).mean()
    else:
        def loss_fn(x, w):
            logits = (x.astype(jnp.float32) @ w.astype(jnp.float32))
            lse = jax.nn.logsumexp(logits, axis=-1)
            picked = jnp.take_along_axis(logits, t[:, None], 1)[:, 0]
            return (lse - picked).mean()

    step = jax.jit(jax.value_and_grad(loss_fn, argnums=(0, 1)))
    try:
        (v, g) = step(x, w)
        jax.block_until_ready((v, g))
    except Exception as e:  # noqa: BLE001 — OOM is a datum here
        msg = str(e)
        if "RESOURCE_EXHAUSTED" in msg or "out of memory" in msg.lower():
            return {"oom": True, "vocab": vocab, "tokens": tokens,
                    "expected_oom": expect_oom,
                    "error": msg.splitlines()[0][:200]}
        raise
    t0 = time.perf_counter()
    for _ in range(iters):
        (v, g) = step(x, w)
    jax.block_until_ready((v, g))
    dt = (time.perf_counter() - t0) / iters
    return {"oom": False, "vocab": vocab, "tokens": tokens, "d": d,
            "ms_per_step": round(dt * 1e3, 2),
            "peak_hbm_mb": _peak_mb(), "loss": round(float(v), 4),
            "expected_oom": expect_oom}


def _run_zero1():
    """Measured single-chip param + adam-state footprint, plus the
    analytic per-chip optimizer memory ZeRO-1 yields over dp (the
    step-time delta needs >1 chip — see module docstring)."""
    import numpy as np
    import mxnet_tpu as mx
    from mxnet_tpu import gluon
    from mxnet_tpu.gluon.model_zoo.vision import resnet50_v1
    from mxnet_tpu.parallel import make_mesh
    from mxnet_tpu.parallel.data_parallel import DataParallelTrainer
    import jax

    net = resnet50_v1()
    net.initialize()
    mesh = make_mesh({"dp": 1}, devices=jax.devices()[:1])
    tr = DataParallelTrainer(net, gluon.loss.SoftmaxCrossEntropyLoss(),
                             "adam", {"learning_rate": 1e-3}, mesh=mesh)
    b = 8
    data = mx.nd.array(np.random.RandomState(0).rand(
        b, 3, 64, 64).astype(np.float32))
    loss = tr.step(data, mx.nd.zeros((b,)))
    loss.asnumpy()
    param_b = sum(int(np.prod(p.shape)) * 4 for p in tr._param_objs)
    # adam: m + v per param, fp32
    opt_b = 2 * param_b
    out = {"param_mb": round(param_b / 1e6, 1),
           "adam_state_mb": round(opt_b / 1e6, 1),
           "peak_hbm_mb_step": _peak_mb(),
           "note": "step-time on/off needs dp>1 (no-op on one chip); "
                   "RS+AG == ring AR wire bytes, savings are state/N"}
    for n in (8, 256):
        out[f"adam_state_mb_per_chip_zero1_dp{n}"] = round(
            opt_b / n / 1e6, 2)
    return out


def run_config(name, kind, **kw):
    from mxnet_tpu.runtime import enable_compile_cache
    enable_compile_cache()
    t0 = time.perf_counter()
    if kind == "accum":
        r = _run_accum(kw["n_micro"])
        r["n_micro"] = kw["n_micro"]
    elif kind == "ce":
        r = _run_ce(kw["impl"], kw["vocab"], kw["tokens"],
                    kw.get("expect_oom", False))
        r["impl"] = kw["impl"]
    else:
        r = _run_zero1()
    r.update(config=name, kind=kind, platform=_platform(),
             wall_s=round(time.perf_counter() - t0, 1))
    print(json.dumps(r), flush=True)
    return r


def main():
    child = os.environ.get("MXTPU_EXP_CHILD")
    if child:   # child: exactly ONE config, never recurse
        cfg = dict(MATRIX[child])
        run_config(child, cfg.pop("kind"), **cfg)
        return
    want = os.environ.get("MXTPU_EXP_CONFIGS")
    names = want.split(",") if want else list(MATRIX)
    for n in names:
        env = dict(os.environ, MXTPU_EXP_CHILD=n)
        line, err = _run_child_graceful(
            [sys.executable, os.path.abspath(__file__)], env, 1500.0)
        print(line if line
              else json.dumps({"config": n, "error": err}), flush=True)


def _run_child_graceful(cmd, env, timeout):
    """TPU-client child with SIGTERM-then-grace termination: the
    child gets 30 s to release the chip before SIGKILL."""
    import signal
    import subprocess
    p = subprocess.Popen(cmd, env=env, stdout=subprocess.PIPE,
                         stderr=subprocess.DEVNULL, text=True,
                         start_new_session=True)
    try:
        out, _ = p.communicate(timeout=timeout)
    except subprocess.TimeoutExpired:
        try:
            os.killpg(p.pid, signal.SIGTERM)
        except ProcessLookupError:
            pass
        try:
            out, _ = p.communicate(timeout=30)
        except subprocess.TimeoutExpired:
            try:
                os.killpg(p.pid, signal.SIGKILL)
            except ProcessLookupError:
                pass
            out, _ = p.communicate()
        lines = [l for l in (out or "").splitlines()
                 if l.startswith("{")]
        return (lines[-1] if lines else None), f"timeout after {timeout}s"
    lines = [l for l in (out or "").splitlines() if l.startswith("{")]
    return (lines[-1] if lines else None), "no output"


if __name__ == "__main__":
    main()
