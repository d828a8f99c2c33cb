"""Flash attention long-sequence evidence (VERDICT r3 #2).

For L in {2k, 4k, 8k} (bf16, single chip) measures, each config in its
OWN subprocess (an OOM must not take the other configs down).  A chip
belongs to one process at a time: the parent never touches JAX, and the
children run one after another.

  - flash:  the Pallas streaming kernel (ops/flash_attention.py)
  - scan:   the blockwise lax.scan fallback (same O(L*bk) memory)
  - naive:  materialized softmax(QK^T)V — the O(L^2) score tensor every
            framework pays without a streaming kernel; at large L this
            is the config that dies of RESOURCE_EXHAUSTED while flash
            keeps running, which is the kernel's reason to exist

Per config: wall ms/call and the device peak HBM (jax memory_stats).
Prints one JSON line.

Usage: python tools/flash_long_seq.py [--ls 2048,4096,8192] [--bh 8]
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _child():
    import math
    import time
    import numpy as np
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.runtime import enable_compile_cache
    enable_compile_cache()
    from mxnet_tpu.ops.flash_attention import _flash, _scan_forward

    impl = os.environ["MXTPU_FLASH_IMPL"]
    L = int(os.environ["MXTPU_FLASH_L"])
    bh = int(os.environ.get("MXTPU_FLASH_BH", "8"))
    dhead = int(os.environ.get("MXTPU_FLASH_D", "64"))
    iters = int(os.environ.get("MXTPU_FLASH_ITERS", "5"))
    scale = 1.0 / math.sqrt(dhead)

    rng = np.random.RandomState(7)
    q, k, v = (jnp.asarray(rng.randn(bh, L, dhead), jnp.bfloat16)
               for _ in range(3))

    if impl == "flash":
        fn = jax.jit(lambda q, k, v: _flash(q, k, v, False, scale))
    elif impl == "scan":
        fn = jax.jit(lambda q, k, v: _scan_forward(
            q, k, v, False, scale, min(256, L))[0])
    else:   # naive: materialized (L, L) scores
        def naive(q, k, v):
            s = jnp.einsum("bqd,bkd->bqk", q, k) * scale
            return jnp.einsum("bqk,bkd->bqd",
                              jax.nn.softmax(s, axis=-1), v)
        fn = jax.jit(naive)

    out = {"impl": impl, "L": L, "platform": jax.devices()[0].platform}
    try:
        fn(q, k, v).block_until_ready()     # compile + first run
        t0 = time.perf_counter()
        for _ in range(iters):
            y = fn(q, k, v)
        y.block_until_ready()
        out["ms"] = round((time.perf_counter() - t0) / iters * 1e3, 2)
        try:
            stats = jax.local_devices()[0].memory_stats()
            out["peak_hbm_gb"] = round(
                stats.get("peak_bytes_in_use", 0) / 1e9, 3)
        except Exception:  # noqa: BLE001 — CPU backend has no stats
            out["peak_hbm_gb"] = None
        out["ok"] = True
    except Exception as e:  # noqa: BLE001 — OOM is a RESULT here
        msg = str(e)
        out["ok"] = False
        out["oom"] = "RESOURCE_EXHAUSTED" in msg or "out of memory" in msg
        out["error"] = msg[:200]
    print("CHILD " + json.dumps(out), flush=True)


def child_env(impl, L, bh=8, base=None, block_q=None, block_kv=None):
    """Env for one (impl, L) child — the single source of the child
    protocol.  ``block_q`` /
    ``block_kv`` pin the Pallas block-size knobs (MXTPU_FLASH_BLOCK_Q/
    KV) for the autotune sweep."""
    env = dict(base if base is not None else os.environ)
    if block_q is not None:
        env["MXTPU_FLASH_BLOCK_Q"] = str(block_q)
    if block_kv is not None:
        env["MXTPU_FLASH_BLOCK_KV"] = str(block_kv)
    env.update({"MXTPU_FLASH_CHILD": "1", "MXTPU_FLASH_IMPL": impl,
                "MXTPU_FLASH_L": str(L), "MXTPU_FLASH_BH": str(bh),
                # prepend REPO, keep the ambient path; no empty
                # components — an empty PYTHONPATH element means cwd and
                # can shadow stdlib
                "PYTHONPATH": os.pathsep.join(
                    [REPO] + [p for p in
                              env.get("PYTHONPATH", "").split(os.pathsep)
                              if p])})
    return env


def parse_child_line(text):
    """Extract the child's CHILD-prefixed JSON result, or None."""
    for line in text.splitlines():
        if line.startswith("CHILD "):
            try:
                return json.loads(line[6:])
            except ValueError:
                return None
    return None


def sweep(ls=(2048, 4096, 8192), bh=8, impls=("flash", "scan", "naive")):
    results = []
    for L in ls:
        for impl in impls:
            try:
                r = subprocess.run(
                    [sys.executable, os.path.abspath(__file__)],
                    capture_output=True, text=True, timeout=900,
                    env=child_env(impl, L, bh))
            except subprocess.TimeoutExpired:
                # a hung config must not discard the results already won
                results.append({"impl": impl, "L": L, "ok": False,
                                "error": "timeout (900s)"})
                continue
            parsed = parse_child_line(r.stdout)
            if parsed is not None:
                results.append(parsed)
            else:
                results.append({"impl": impl, "L": L, "ok": False,
                                "error": (r.stderr or "no output")[-200:]})
    return results


_BLOCK_GRID = ((128, 128), (256, 256), (512, 512), (256, 512),
               (512, 256), (512, 1024), (1024, 512))


def block_sweep(L=2048, bh=8, blocks=_BLOCK_GRID):
    """Autotune the Pallas flash block sizes at sequence length ``L``
    (ISSUE 6 satellite — the 1.03x follow-up): run the flash impl once
    per (BLOCK_Q, BLOCK_KV) candidate, each in its own subprocess with
    ``MXTPU_FLASH_BLOCK_Q/KV`` pinned, and report every timing plus the
    winner."""
    results = []
    for bq, bkv in blocks:
        if bq > L or bkv > L:
            continue
        try:
            r = subprocess.run(
                [sys.executable, os.path.abspath(__file__)],
                capture_output=True, text=True, timeout=900,
                env=child_env("flash", L, bh, block_q=bq, block_kv=bkv))
        except subprocess.TimeoutExpired:
            results.append({"block_q": bq, "block_kv": bkv, "ok": False,
                            "error": "timeout (900s)"})
            continue
        parsed = parse_child_line(r.stdout)
        if parsed is None:
            parsed = {"ok": False,
                      "error": (r.stderr or "no output")[-200:]}
        parsed.update({"block_q": bq, "block_kv": bkv})
        results.append(parsed)
    timed = [r for r in results if r.get("ok") and "ms" in r]
    best = min(timed, key=lambda r: r["ms"]) if timed else None
    out = {"L": L, "bh": bh, "sweep": results}
    if best is not None:
        out["best"] = {"block_q": best["block_q"],
                       "block_kv": best["block_kv"], "ms": best["ms"]}
        default = next((r for r in timed
                        if r["block_q"] == 512 and r["block_kv"] == 512),
                       None)
        if default is not None and best["ms"] > 0:
            out["best"]["speedup_vs_default"] = round(
                default["ms"] / best["ms"], 3)
    return out


def summarize(results):
    by = {(r["L"], r["impl"]): r for r in results}
    summary = []
    for L in sorted({r["L"] for r in results}):
        f, s, n = by.get((L, "flash")), by.get((L, "scan")), \
            by.get((L, "naive"))
        row = {"L": L}
        if f and f.get("ok"):
            row["flash_ms"] = f["ms"]
            row["flash_peak_hbm_gb"] = f.get("peak_hbm_gb")
        if s and s.get("ok") and f and f.get("ok"):
            row["scan_ms"] = s["ms"]
            row["flash_speedup_vs_scan"] = round(s["ms"] / f["ms"], 2)
        if n:
            row["naive_ok"] = n.get("ok", False)
            if n.get("ok"):
                row["naive_ms"] = n["ms"]
                row["naive_peak_hbm_gb"] = n.get("peak_hbm_gb")
            elif n.get("oom"):
                row["naive_oom"] = True   # the footprint evidence
        summary.append(row)
    return summary


def main():
    if os.environ.get("MXTPU_FLASH_CHILD") == "1":
        _child()
        return
    ap = argparse.ArgumentParser()
    ap.add_argument("--ls", default="2048,4096,8192")
    ap.add_argument("--bh", type=int, default=8)
    ap.add_argument("--impls", default="flash,scan,naive")
    ap.add_argument("--block-sweep", action="store_true",
                    help="autotune MXTPU_FLASH_BLOCK_Q/KV for the flash "
                         "impl at the FIRST --ls length instead of the "
                         "impl sweep")
    args = ap.parse_args()
    ls = tuple(int(x) for x in args.ls.split(","))
    if args.block_sweep:
        print(json.dumps(block_sweep(L=ls[0], bh=args.bh)))
        return
    results = sweep(ls, bh=args.bh, impls=tuple(args.impls.split(",")))
    print(json.dumps({"sweep": results, "summary": summarize(results)}))


if __name__ == "__main__":
    main()
