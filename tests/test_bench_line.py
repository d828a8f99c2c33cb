"""The last line bench.py prints is the compact headline.

These tests pin its contract: whatever the payload (a full run, or one
adversarially bloated), the FINAL line is valid JSON under 1800 bytes
with the headline metric intact.  (Upstream analogue: the perf scripts'
one-line summary contract, SURVEY.md §6.)
"""
import json
import os

import pytest

import bench


def _assert_headline(line: str):
    assert len(line) < 1800, f"headline line is {len(line)} bytes"
    obj = json.loads(line)
    for k in ("metric", "value", "unit", "vs_baseline"):
        assert k in obj, f"missing core key {k}"
    return obj


def _success_payload():
    """A realistic full-TPU-run payload with every extra attached."""
    from mxnet_tpu.parallel import zero
    return {
        "metric": "resnet50_train_images_per_sec", "value": 2068.4,
        "unit": "img/s", "vs_baseline": 1.59, "platform": "tpu",
        "platform_requested": "tpu", "platform_actual": "tpu",
        "telemetry_schema_version": 1,
        "batch": 256, "dtype": "bf16", "data": "synthetic",
        "s2d_stem": True, "mfu": 0.235, "tflops_delivered": 46.3,
        "steps_per_call": 16, "dispatch_ms_per_step": 0.41,
        "flops_source": "xla_cost_analysis",
        "chip_peak_tflops_bf16": 197.0,
        "comm": zero.comm_block(
            dp=8, wire_dtype="bf16", buckets=4, bucket_mb=32.0,
            bytes_reduced_per_step=51_200_000,
            bytes_gathered_per_step=102_400_000,
            grad_bytes_fp32=102_400_000, collective_ms=1.84,
            est_ici_gb_s=83.5, overlap_efficiency=0.97, zero1=True,
            state_bytes_per_chip=12_800_000,
            state_bytes_replicated=102_400_000),
        "input_pipeline": {"decode_thread_sweep": [
            {"threads": t, "img_s": 410.0} for t in (1, 2, 4, 8)]},
        "extra": {
            "bert": {"metric": "bert_base_train_samples_per_sec",
                     "value": 1162.0, "unit": "samples/s", "mfu": 0.397,
                     "batch": 64, "seq": 128,
                     "note": "x" * 400},
            "resnet_rec_pipeline": {"metric": "resnet50_rec_pipeline",
                                    "value": 401.2,
                                    "input_pipeline": {"stats": "y" * 600}},
            "kvstore_bandwidth": {"allreduce": {"per_key_gb_s": 1.9},
                                  "allgather": {"per_key_gb_s": 0.9},
                                  "per_key_speedup": 2.1,
                                  "note": "z" * 300},
            "tpu_bandwidth": {"payload_mb": 64, "h2d_gb_s": 11.2,
                              "d2h_gb_s": 5.1, "hbm_copy_gb_s": 410.0,
                              "psum_1dev_ms": 0.21},
            "llama_decode": {"model": "llama-decode", "batch": 8,
                             "tokens_per_sec": 9000.1,
                             "ms_per_step": 0.9, "note": "w" * 200},
            "scaling_projection": {
                "projection": [
                    {"chips": n, "projected_efficiency": e}
                    for n, e in ((8, 0.991), (64, 0.9905), (256, 0.990))],
                "note": "p" * 400},
            "memory_levers": {"zero1_hbm_savings_mb": 150.1,
                              "blocked_ce_peak_mb": 312.0},
        },
    }


def test_success_line_parses_and_fits():
    obj = _assert_headline(bench._compact_line(_success_payload()))
    assert obj["value"] == 2068.4
    assert obj["platform"] == "tpu"
    assert obj["mfu"] == 0.235
    # multi-step compiled training evidence (ISSUE 6) survives
    assert obj["steps_per_call"] == 16
    assert obj["dispatch_ms_per_step"] == 0.41
    # sharded-sync evidence survives compaction when zero1 ran
    assert obj["comm_ms"] == 1.84
    assert obj["comm_gb_s"] == 83.5
    assert obj["comm_mb_reduced"] == 51.2
    # scalar summaries survive compaction
    assert obj["bert_samples_s"] == 1162.0
    assert obj["decode_tok_s"] == 9000.1
    assert obj["proj_eff_256"] == 0.990
    # future extras (memory levers) surface via the generic sweep
    assert obj["memory_levers.zero1_hbm_savings_mb"] == 150.1


def test_adversarially_bloated_payload_still_fits():
    p = _success_payload()
    # hundreds of scalar extras: budget must hold regardless
    p["extra"]["sweep"] = {f"k{i}": i * 1.5 for i in range(500)}
    p["error"] = "e" * 5000
    _assert_headline(bench._compact_line(p))


def test_minimal_error_payload():
    line = bench._compact_line(
        {"metric": "resnet50_train_images_per_sec", "value": 0.0,
         "unit": "img/s", "vs_baseline": 0.0})
    obj = _assert_headline(line)
    assert obj["value"] == 0.0


# ----------------------------------------------------------------------
# the `comm` block schema (ISSUE 3): regression-tested on CPU — the
# sharded-sync observability must ship with every field present (zeros
# are fine) so a TPU round can't discover a broken schema
# ----------------------------------------------------------------------

_COMM_KEYS = {
    "zero1", "dp", "wire_dtype", "buckets", "bucket_mb",
    "bytes_reduced_per_step", "bytes_gathered_per_step",
    "grad_bytes_fp32", "collective_ms", "est_ici_gb_s",
    "overlap_efficiency", "overlap_comm", "exposed_comm_ms",
    "overlap_frac", "state_bytes_per_chip",
    "state_bytes_replicated",
}


def test_comm_block_schema_is_stable():
    from mxnet_tpu.parallel import zero
    blk = zero.comm_block()
    assert set(blk) == _COMM_KEYS
    # static accounting defaults are zeros / fp32 — the CPU shape
    assert blk["dp"] == 1 and not blk["zero1"]
    assert blk["wire_dtype"] == "fp32"
    # MEASURED fields are null when nothing measured (ISSUE 6 honesty
    # fix: a CPU zero must not read as "measured: comm is free")
    for k in ("collective_ms", "est_ici_gb_s", "overlap_efficiency",
              "exposed_comm_ms", "overlap_frac"):
        assert blk[k] is None, k
    assert blk["overlap_comm"] is False
    # measured values still round-trip as numbers
    blk2 = zero.comm_block(collective_ms=1.8444, overlap_frac=0.51234)
    assert blk2["collective_ms"] == 1.844
    assert blk2["overlap_frac"] == 0.5123
    assert json.loads(json.dumps(blk)) == blk


def test_pipeline_probe_emits_comm_block():
    """tools/bench_pipeline.py emits the block end-to-end: on the forced
    8-device CPU mesh the sharded pipeline actually runs and the
    collective time is measured; on 1 device it's the zeros shape."""
    import jax
    from tools.bench_pipeline import comm_probe
    payload = comm_probe(batch=16, iters=2)
    comm = payload["comm"]
    assert set(comm) == _COMM_KEYS
    assert len(json.dumps(payload)) < 1800
    if len(jax.devices()) >= 8:
        assert comm["zero1"] and comm["dp"] == 8
        assert comm["bytes_reduced_per_step"] > 0
        assert comm["collective_ms"] > 0
    else:
        assert comm["bytes_reduced_per_step"] == 0
        # nothing measured on 1 device: null, not a fake zero
        assert comm["collective_ms"] is None


def test_overlap_probe_emits_schema_and_timings():
    """tools/bench_pipeline.py overlap_probe: the comm block carries the
    with-vs-without-overlap fields end-to-end.  On the forced 8-device
    CPU mesh the three step builds (overlapped / monolithic /
    compute-only) actually compile and time; zeros are allowed on CPU —
    the SCHEMA is the tier-1 contract, the >0 numbers are TPU evidence."""
    import jax
    from tools.bench_pipeline import overlap_probe
    payload = overlap_probe(batch=16, iters=2)
    comm = payload["comm"]
    assert set(comm) == _COMM_KEYS
    assert len(json.dumps(payload)) < 1800
    if len(jax.devices()) >= 8:
        assert comm["zero1"] and comm["overlap_comm"]
        assert comm["exposed_comm_ms"] >= 0.0
        assert 0.0 <= comm["overlap_frac"] <= 1.0
        ov = payload["overlap"]
        for k in ("overlapped_step_ms", "monolithic_step_ms",
                  "compute_only_step_ms"):
            assert ov[k] > 0
    else:
        # probe could not run: nulls, never fake zeros (ISSUE 6)
        assert comm["exposed_comm_ms"] is None
        assert comm["overlap_frac"] is None


def test_comm_mb_reduced_dropped_when_replicated():
    """A psum-path run (zero1 False) keeps comm_* out of the headline."""
    p = _success_payload()
    p["comm"]["zero1"] = False
    obj = json.loads(bench._compact_line(p))
    assert "comm_ms" not in obj and "comm_mb_reduced" not in obj


def test_null_measured_fields_stay_out_of_headline():
    """A zero1 block whose measured fields are null (nothing measured)
    must not put nulls — or fake zeros — into the compact line."""
    from mxnet_tpu.parallel import zero
    p = _success_payload()
    p["comm"] = zero.comm_block(dp=8, zero1=True, buckets=4,
                                bytes_reduced_per_step=1000)
    p["dispatch_ms_per_step"] = None
    obj = json.loads(bench._compact_line(p))
    assert "comm_ms" not in obj and "comm_overlap_frac" not in obj
    assert "dispatch_ms_per_step" not in obj
    assert obj["comm_mb_reduced"] == 0.0   # static accounting still real


# ----------------------------------------------------------------------
# multi-step dispatch evidence (ISSUE 6): the dispatch_probe subcommand
# and the steps_per_call plumbing
# ----------------------------------------------------------------------

def test_dispatch_probe_schema_and_monotone_shrink():
    """K steps scanned into one dispatch must shrink the per-step
    dispatch tax monotonically K=1 -> 16 on CPU — the acceptance
    criterion the probe exists to demonstrate."""
    from tools.bench_pipeline import dispatch_probe
    payload = dispatch_probe(ks=(1, 4, 16), steps=32, repeats=2)
    assert payload["metric"] == "pipeline_dispatch_probe"
    assert len(json.dumps(payload)) < 1800
    rows = {r["k"]: r for r in payload["rows"]}
    assert set(rows) == {1, 4, 16}
    for r in rows.values():
        assert r["step_ms"] > 0
        assert r["dispatch_ms_per_step"] >= 0.0
    # small absolute slack: sub-0.02ms jitter must not flake the gate
    eps = 0.02
    assert rows[1]["dispatch_ms_per_step"] >= \
        rows[4]["dispatch_ms_per_step"] - eps
    assert rows[4]["dispatch_ms_per_step"] >= \
        rows[16]["dispatch_ms_per_step"] - eps
    # the headline claim: one-dispatch-per-step pays measurably more
    # host time than 16-steps-per-dispatch
    assert rows[1]["step_ms"] >= rows[16]["step_ms"]


def test_main_refuses_a_platform_nobody_asked_for(monkeypatch, capsys):
    """JAX fell back to the CPU and the caller did not ask for it: the
    run fails before it measures anything and prints no result."""
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    monkeypatch.setattr(bench, "_run_bench", lambda: pytest.fail("ran"))
    assert bench.main() != 0
    out = capsys.readouterr()
    assert out.out == "" and "not a TPU" in out.err


def test_main_does_not_swallow_a_failed_run(monkeypatch, capsys):
    """An exception ends the run (non-zero exit through the traceback):
    no JSON line, no fallback child."""
    def boom():
        raise RuntimeError("step failed")
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    monkeypatch.setattr(bench, "_run_bench", boom)
    with pytest.raises(RuntimeError, match="step failed"):
        bench.main()
    assert capsys.readouterr().out == ""


# ----------------------------------------------------------------------
# the `serving` block schema (ISSUE 7): config always real, measured
# fields null-when-unmeasured — a CPU run can't fake serving latency
# ----------------------------------------------------------------------

_SERVING_KEYS = {
    "max_batch", "block_size", "buckets", "quantized", "continuous",
    "requests", "p50_ms", "p99_ms", "ttft_p50_ms", "tokens_s",
    "tokens_s_chip", "occupancy", "tokens_per_step",
    "compiles_after_warmup", "cache_utilization",
    # ISSUE 12 front-end fields
    "chunked_prefill", "router_replicas", "prefix_hit_rate",
    "router_p99_ms",
    # ISSUE 17 speculative-decoding fields
    "speculative", "paged_attn", "spec_accept_rate",
    "tokens_per_dispatch",
    # ISSUE 18 sharded/disaggregated fleet fields
    "tp_shards", "disaggregated", "handoff_ms",
    "prefill_pool_occupancy", "decode_pool_occupancy",
    # ISSUE 20 low-precision KV fields
    "kv_dtype", "kv_capacity_ratio", "kv_decode_drift",
}


def test_serving_block_schema_is_stable():
    from mxnet_tpu.serving import serving_block
    blk = serving_block()
    assert set(blk) == _SERVING_KEYS
    # MEASURED fields are null when nothing was measured
    for k in ("p50_ms", "p99_ms", "ttft_p50_ms", "tokens_s",
              "tokens_s_chip", "occupancy", "tokens_per_step",
              "compiles_after_warmup", "cache_utilization",
              "prefix_hit_rate", "router_p99_ms", "spec_accept_rate",
              "tokens_per_dispatch", "handoff_ms",
              "prefill_pool_occupancy", "decode_pool_occupancy",
              "kv_capacity_ratio", "kv_decode_drift"):
        assert blk[k] is None, k
    # CONFIG fields are always real (front-end off by default)
    assert blk["chunked_prefill"] is False
    assert blk["router_replicas"] == 0
    assert blk["speculative"] is False
    assert blk["paged_attn"] is False
    assert blk["tp_shards"] == 0
    assert blk["disaggregated"] is False
    assert blk["kv_dtype"] == "fp32"
    # measured values round-trip, rounded
    blk2 = serving_block(p99_ms=12.3456, tokens_s_chip=901.239,
                         occupancy=0.87654, compiles_after_warmup=0,
                         chunked_prefill=True, router_replicas=4,
                         prefix_hit_rate=0.98765, router_p99_ms=77.7777,
                         speculative=True, paged_attn=True,
                         spec_accept_rate=0.61239,
                         tokens_per_dispatch=2.71828,
                         tp_shards=2, disaggregated=True,
                         handoff_ms=0.12345,
                         prefill_pool_occupancy=0.43219,
                         decode_pool_occupancy=0.87654)
    assert blk2["p99_ms"] == 12.346
    assert blk2["tokens_s_chip"] == 901.2
    assert blk2["occupancy"] == 0.8765
    assert blk2["compiles_after_warmup"] == 0
    assert blk2["chunked_prefill"] is True
    assert blk2["router_replicas"] == 4
    assert blk2["prefix_hit_rate"] == 0.9877
    assert blk2["router_p99_ms"] == 77.778
    assert blk2["speculative"] is True
    assert blk2["paged_attn"] is True
    assert blk2["spec_accept_rate"] == 0.6124
    assert blk2["tokens_per_dispatch"] == 2.718
    assert blk2["tp_shards"] == 2
    assert blk2["disaggregated"] is True
    assert blk2["handoff_ms"] == 0.123
    assert blk2["prefill_pool_occupancy"] == 0.4322
    assert blk2["decode_pool_occupancy"] == 0.8765
    assert json.loads(json.dumps(blk)) == blk


def test_bench_serving_on_cpu_is_nulls_not_zeros():
    """bench.py's serving block on a CPU host: config real, every
    latency/throughput field null (the CPU-scale evidence lives in the
    tier-1 serve_loadgen smoke, not in fake bench zeros)."""
    import jax
    if jax.devices()[0].platform != "cpu":
        return
    blk = bench._bench_serving()
    for k in ("p50_ms", "p99_ms", "tokens_s_chip", "occupancy",
              "spec_accept_rate", "tokens_per_dispatch"):
        assert blk[k] is None, k
    assert blk["max_batch"] > 0 and blk["block_size"] > 0
    assert "note" in blk


def test_serving_compact_keys_surface_when_measured():
    from mxnet_tpu.serving import serving_block
    p = _success_payload()
    p["extra"]["serving"] = serving_block(
        max_batch=8, block_size=16, buckets=(16, 32, 64),
        requests=32, p50_ms=41.2, p99_ms=88.7, tokens_s=9120.4,
        tokens_s_chip=9120.4, occupancy=0.91, tokens_per_step=7.3,
        compiles_after_warmup=0, chunked_prefill=True,
        router_replicas=4, prefix_hit_rate=0.97, router_p99_ms=92.3,
        tp_shards=2, disaggregated=True, handoff_ms=0.42,
        prefill_pool_occupancy=0.55, decode_pool_occupancy=0.83)
    obj = _assert_headline(bench._compact_line(p))
    assert obj["serve_tok_s"] == 9120.4
    assert obj["serve_p99_ms"] == 88.7
    assert obj["serve_occupancy"] == 0.91
    assert obj["serve_prefix_hit"] == 0.97
    assert obj["router_p99_ms"] == 92.3
    assert obj["serve_handoff_ms"] == 0.42
    assert obj["serve_prefill_occ"] == 0.55
    assert obj["serve_decode_occ"] == 0.83


def test_serving_nulls_stay_out_of_headline():
    from mxnet_tpu.serving import serving_block
    p = _success_payload()
    p["extra"]["serving"] = serving_block(max_batch=8, block_size=16,
                                          buckets=(16, 32))
    obj = json.loads(bench._compact_line(p))
    assert "serve_tok_s" not in obj
    assert "serve_p99_ms" not in obj
    assert "serve_occupancy" not in obj
    assert "serve_prefix_hit" not in obj
    assert "router_p99_ms" not in obj
    assert "serve_handoff_ms" not in obj
    assert "serve_prefill_occ" not in obj
    assert "serve_decode_occ" not in obj


# ----------------------------------------------------------------------
# the `elastic` block schema (ISSUE 8): config/counters always real,
# measured transition timings null-when-unmeasured — a CPU run can't
# pass off an absent measurement as "resharding is free"
# ----------------------------------------------------------------------

_ELASTIC_KEYS = {
    "enabled", "dp", "membership_epoch", "transitions", "degraded",
    "reshard_ms", "pause_ms", "drain_ms", "drains", "pending_notices",
    "autoscale_decisions",
}


def test_elastic_block_schema_is_stable():
    from mxnet_tpu.elastic import elastic_block
    blk = elastic_block()
    assert set(blk) == _ELASTIC_KEYS
    for k in ("reshard_ms", "pause_ms", "drain_ms",
              "autoscale_decisions"):
        assert blk[k] is None, k
    assert blk["enabled"] is False and blk["transitions"] == 0
    assert blk["drains"] == 0 and blk["pending_notices"] == 0
    blk2 = elastic_block(enabled=True, dp=4, membership_epoch=2,
                         transitions=1, reshard_ms=73.7777,
                         pause_ms=74.1234, drain_ms=5.5555,
                         drains=1, autoscale_decisions=3)
    assert blk2["reshard_ms"] == 73.778
    assert blk2["pause_ms"] == 74.123
    assert blk2["drain_ms"] == 5.556
    assert blk2["autoscale_decisions"] == 3
    assert json.loads(json.dumps(blk)) == blk


def test_bench_elastic_on_cpu_is_nulls_not_zeros():
    """bench.py's elastic block on a CPU host: the measured transition
    timings stay null (the bitwise correctness evidence lives in the
    tier-1 chaos elastic suite, not in fake bench numbers).  The ISSUE
    13 fields keep the same honesty: no notice drain / autoscale loop
    ran, so drain_ms and autoscale_decisions are null, not zero."""
    import jax
    if jax.devices()[0].platform != "cpu":
        return
    blk = bench._bench_elastic()
    assert blk["reshard_ms"] is None
    assert blk["pause_ms"] is None
    assert blk["drain_ms"] is None
    assert blk["autoscale_decisions"] is None
    assert "note" in blk


def test_elastic_compact_keys_surface_when_measured():
    from mxnet_tpu.elastic import elastic_block
    p = _success_payload()
    p["extra"]["elastic"] = elastic_block(
        enabled=True, dp=4, membership_epoch=2, transitions=1,
        reshard_ms=73.8, pause_ms=74.1)
    obj = _assert_headline(bench._compact_line(p))
    assert obj["elastic_reshard_ms"] == 73.8
    assert obj["elastic_pause_ms"] == 74.1
    assert obj["elastic_epoch"] == 2


def test_elastic_nulls_stay_out_of_headline():
    from mxnet_tpu.elastic import elastic_block
    p = _success_payload()
    p["extra"]["elastic"] = elastic_block(enabled=True, dp=8)
    obj = json.loads(bench._compact_line(p))
    assert "elastic_reshard_ms" not in obj
    assert "elastic_pause_ms" not in obj


# ----------------------------------------------------------------------
# the `fleet` block schema (ISSUE 15): config always real, measured
# skew/scrape fields null-when-unmeasured — a single-process run can't
# pass off "no fleet to scrape" as "zero skew measured"
# ----------------------------------------------------------------------

_FLEET_KEYS = {
    "fleet_schema_version", "enabled", "ranks", "slowest_rank",
    "step_ms_skew", "scrape_ms", "stragglers", "epoch_desync",
    "scrape_dead",
}


def test_fleet_block_schema_is_stable():
    from mxnet_tpu.telemetry.fleet import (fleet_block,
                                           FLEET_SCHEMA_VERSION)
    blk = fleet_block()
    assert set(blk) == _FLEET_KEYS
    assert blk["fleet_schema_version"] == FLEET_SCHEMA_VERSION
    for k in ("slowest_rank", "step_ms_skew", "scrape_ms",
              "stragglers", "epoch_desync", "scrape_dead"):
        assert blk[k] is None, k
    assert blk["enabled"] is False and blk["ranks"] == 0
    blk2 = fleet_block(enabled=True, ranks=4, slowest_rank=2,
                       step_ms_skew=3.14159, scrape_ms=12.5555,
                       stragglers=1, epoch_desync=False, scrape_dead=1)
    assert blk2["step_ms_skew"] == 3.1416
    assert blk2["scrape_ms"] == 12.556
    assert blk2["slowest_rank"] == 2 and blk2["scrape_dead"] == 1
    assert json.loads(json.dumps(blk)) == blk


def test_bench_fleet_single_process_is_nulls_not_zeros(monkeypatch):
    """bench.py's fleet block without MXTPU_FLEET_ADDRS: there is no
    fleet to scrape, so every measured field is null — the correctness
    evidence lives in the tier-1 chaos fleet suite."""
    monkeypatch.delenv("MXTPU_FLEET_ADDRS", raising=False)
    blk = bench._bench_fleet()
    assert blk["slowest_rank"] is None
    assert blk["step_ms_skew"] is None
    assert blk["scrape_ms"] is None
    assert blk["stragglers"] is None
    assert "note" in blk


def test_fleet_compact_keys_surface_when_measured():
    from mxnet_tpu.telemetry.fleet import fleet_block
    p = _success_payload()
    p["extra"]["fleet"] = fleet_block(
        enabled=True, ranks=4, slowest_rank=2, step_ms_skew=3.1,
        scrape_ms=12.5, stragglers=1)
    obj = _assert_headline(bench._compact_line(p))
    assert obj["fleet_slowest_rank"] == 2
    assert obj["fleet_skew"] == 3.1
    assert obj["fleet_scrape_ms"] == 12.5


def test_fleet_nulls_stay_out_of_headline():
    from mxnet_tpu.telemetry.fleet import fleet_block
    p = _success_payload()
    p["extra"]["fleet"] = fleet_block(enabled=True, ranks=1)
    obj = json.loads(bench._compact_line(p))
    assert "fleet_slowest_rank" not in obj
    assert "fleet_skew" not in obj
    assert "fleet_scrape_ms" not in obj


def test_bench_diff_gates_fleet_schema_drift(tmp_path, capsys):
    """tools/bench_diff.py refuses (exit 2) to compare payloads whose
    fleet blocks carry different fleet_schema_versions — the ISSUE 11
    telemetry-schema discipline extended to the fleet snapshot."""
    from tools import bench_diff
    from mxnet_tpu.telemetry.fleet import fleet_block
    base = {"metric": "m", "value": 1.0, "platform": "cpu",
            "telemetry_schema_version": 1,
            "extra": {"fleet": fleet_block(enabled=True, ranks=2)}}
    drift = json.loads(json.dumps(base))
    drift["extra"]["fleet"]["fleet_schema_version"] += 1
    a, b = tmp_path / "a.json", tmp_path / "b.json"
    a.write_text(json.dumps(base))
    b.write_text(json.dumps(drift))
    rc = bench_diff.main([str(a), str(b), "--quiet"])
    out = capsys.readouterr().out
    assert rc == 2
    assert "fleet_schema_drift" in out
    # same fleet schema compares fine
    b.write_text(json.dumps(base))
    assert bench_diff.main([str(a), str(b), "--quiet"]) == 0


# ----------------------------------------------------------------------
# telemetry stamping (ISSUE 9): every bench JSON carries the telemetry
# schema version, and telemetry-derived block fields keep the PR 6
# null-when-unmeasured honesty rules
# ----------------------------------------------------------------------

def test_mfu_helpers_delegate_to_shared_costmodel():
    """ISSUE 14: flops_source/mfu come from telemetry/costmodel.py —
    the bench-local helpers are thin wrappers over the ONE cost model
    the trainer's live gauges use, and the payload they produce for
    the same inputs is byte-identical to before the lift."""
    from mxnet_tpu.telemetry import costmodel
    assert bench._resnet_train_flops_per_img() == \
        costmodel.resnet_train_flops_per_img()
    assert bench._bert_train_flops_per_sample(128, layers=2) == \
        costmodel.bert_train_flops_per_sample(128, layers=2)
    assert bench._chip_peak_flops(None) is None or True
    a = bench._attach_mfu({"batch": 16}, 2e9, 321.5)
    b = costmodel.attach_mfu({"batch": 16}, 2e9, 321.5)
    assert json.dumps(a, sort_keys=True) == json.dumps(b, sort_keys=True)
    # the exact pre-lift shape on a CPU host: analytic source, no mfu
    assert a["flops_source"] == "analytic_2mac"
    assert a["tflops_delivered"] == round(2e9 * 321.5 / 1e12, 2)


def test_mfu_live_null_when_unmeasured_on_cpu():
    """The compact line's ``mfu_live`` keeps the PR 6 honesty rule: on
    a CPU host the trainer never stamps `train.mfu`, so the stamped
    field is null and stays OUT of the headline."""
    from mxnet_tpu import telemetry
    if not telemetry.enabled():
        return
    telemetry.reset()
    r = bench._stamp_live_mfu({"metric": "x"})
    assert r["mfu_live"] is None
    p = _success_payload()
    p["mfu_live"] = None
    assert "mfu_live" not in json.loads(bench._compact_line(p))
    # measured (TPU round / env-pinned peak): the key surfaces
    p["mfu_live"] = 0.233
    obj = _assert_headline(bench._compact_line(p))
    assert obj["mfu_live"] == 0.233
    # and the live gauge rides through the stamp when present
    telemetry.set_gauge("train.mfu", 0.41)
    assert bench._stamp_live_mfu({})["mfu_live"] == 0.41
    telemetry.reset()


def test_telemetry_schema_version_stamped():
    from mxnet_tpu.telemetry import SCHEMA_VERSION
    r = bench._stamp_telemetry({"metric": "x"})
    assert r["telemetry_schema_version"] == SCHEMA_VERSION
    # the stamp survives compaction into the driver headline
    obj = _assert_headline(bench._compact_line(_success_payload()))
    assert obj["telemetry_schema_version"] == 1


@pytest.mark.slow   # builds two engines; the telemetry read-through
# discipline is gated fast in test_telemetry.py
def test_loadgen_compiles_counter_reads_through_telemetry():
    """The loadgen's compiles_after_warmup is a before/after DELTA off
    the process registry (one source of truth), so a second engine in
    the same process cannot inherit the first one's count."""
    from mxnet_tpu import telemetry
    if not telemetry.enabled():
        return
    telemetry.reset()
    # simulate an earlier engine's post-warmup compile in this process
    telemetry.inc("serving.compiles_after_warmup", 3)
    import tools.serve_loadgen as slg
    payload = slg.run_loadgen(n_requests=2, max_batch=2, block_size=8,
                              max_context=64, mode="continuous",
                              smoke=True)
    blk = payload["serving"]
    # the measured WINDOW saw zero compiles even though the process
    # counter started at 3 — and the KV utilization gauge rode along
    assert blk["compiles_after_warmup"] == 0
    assert blk["cache_utilization"] is not None


def test_serving_nulls_honesty_survives_telemetry(monkeypatch):
    """With the telemetry kill switch on, serving_block fields fall
    back to the engine's own counters — never fake zeros from an empty
    registry."""
    from mxnet_tpu import telemetry as telem
    was = telem.enabled()
    telem.configure(enabled=False)
    try:
        assert telem.snapshot() == {"schema_version": 1,
                                    "enabled": False}
        assert telem.value("serving.kv_block_utilization") is None
        import jax
        if jax.devices()[0].platform == "cpu":
            blk = bench._bench_serving()
            for k in ("p50_ms", "p99_ms", "tokens_s_chip", "occupancy"):
                assert blk[k] is None, k
    finally:
        telem.configure(enabled=was)


# ---------------------------------------------------------------------------
# parallelism block (ISSUE 11): mesh shape stamped, pp/tp fields honest
# ---------------------------------------------------------------------------

_PAR_KEYS = {"mesh", "mesh_spec", "pp_microbatches", "pp_bubble_frac",
             "tp_collective_ms"}


def test_parallelism_block_schema_is_stable():
    from mxnet_tpu.parallel.mesh import MeshConfig, parallelism_block
    blk = parallelism_block()
    assert set(blk) == _PAR_KEYS
    assert blk["mesh"] == {"dp": 1, "tp": 1, "pp": 1}
    assert blk["mesh_spec"] == "dp1"
    # measured/conditional fields are null-when-absent, never fake zeros
    for k in ("pp_microbatches", "pp_bubble_frac", "tp_collective_ms"):
        assert blk[k] is None, k
    blk3 = parallelism_block(MeshConfig.from_spec("2x2x2"),
                             pp_microbatches=8,
                             pp_bubble_frac=1 / 9)
    assert blk3["mesh"] == {"dp": 2, "tp": 2, "pp": 2}
    assert blk3["mesh_spec"] == "dp2tp2pp2"
    assert blk3["pp_bubble_frac"] == 0.1111
    assert json.loads(json.dumps(blk3)) == blk3


def test_bench_stamps_mesh_and_parallelism():
    """bench.py stamps the trainer's mesh shape into every payload; on
    a flat-dp CPU run the pp/tp fields are nulls (nothing measured, no
    pipeline axis), never zeros."""
    import jax
    if len(jax.devices()) < 8:
        import pytest
        pytest.skip("needs 8 virtual devices")
    from mxnet_tpu import gluon
    from mxnet_tpu.parallel import MeshConfig, DataParallelTrainer
    net = gluon.nn.Dense(4)
    tr = DataParallelTrainer(net, gluon.loss.L2Loss(), "sgd",
                             {"learning_rate": 0.1},
                             mesh_config=MeshConfig.from_spec("dp8"))
    result = {}
    bench._stamp_parallelism(result, tr)
    assert result["mesh"] == {"dp": 8, "tp": 1, "pp": 1}
    par = result["parallelism"]
    assert set(par) == _PAR_KEYS
    assert par["mesh_spec"] == "dp8"
    assert par["pp_bubble_frac"] is None
    assert par["tp_collective_ms"] is None
    # with a pipeline axis the analytic bubble fraction is stamped
    net2 = gluon.nn.HybridSequential()
    net2.add(gluon.nn.Dense(4), gluon.nn.Dense(4))
    tr3 = DataParallelTrainer(net2, gluon.loss.L2Loss(), "sgd",
                              {"learning_rate": 0.1},
                              mesh_config=MeshConfig.from_spec("4x1x2"),
                              pp_microbatches=8)
    result3 = {}
    bench._stamp_parallelism(result3, tr3)
    par3 = result3["parallelism"]
    assert par3["mesh_spec"] == "dp4pp2"
    assert par3["pp_microbatches"] == 8
    assert par3["pp_bubble_frac"] == round(1 / 9, 4)


def test_mesh_spec_surfaces_in_headline():
    payload = _success_payload()
    from mxnet_tpu.parallel.mesh import MeshConfig, parallelism_block
    payload["parallelism"] = parallelism_block(
        MeshConfig.from_spec("dp64tp4"))
    line = bench._compact_line(payload)
    obj = _assert_headline(line)
    assert obj.get("mesh") == "dp64tp4"


# ----------------------------------------------------------------------
# the `lint` block schema (ISSUE 16): the full HB01-HB20 sweep runs
# inside the bench and ships a zero-findings verdict with the line
# ----------------------------------------------------------------------

_LINT_KEYS = {
    "lint_schema_version", "rules_enabled", "files_checked",
    "suppressions", "findings", "ok",
}


@pytest.mark.slow
def test_bench_lint_block_schema_and_zero_findings_gate():
    """The block's schema is stable, the sweep really runs (file and
    rule counts are live), and findings==0 — the measured tree is
    donation-clean.  A finding would flip `ok` and surface in the next
    bench diff."""
    blk = bench._bench_lint()
    assert set(blk) == _LINT_KEYS, set(blk) ^ _LINT_KEYS
    assert blk["lint_schema_version"] == bench.LINT_SCHEMA_VERSION
    assert blk["rules_enabled"] >= 20          # HB01..HB20 shipped
    assert blk["files_checked"] > 50
    assert blk["suppressions"] >= 1            # justified opt-outs exist
    assert blk["findings"] == 0
    assert blk["ok"] is True
    assert "by_rule" not in blk                # only present on findings
    assert json.loads(json.dumps(blk)) == blk


def test_bench_lint_block_rides_the_headline_budget():
    """lint counters are scalars one level deep: the generic headline
    sweep may surface them, and the line stays under the cap."""
    p = _success_payload()
    p["extra"]["lint"] = {
        "lint_schema_version": 1, "rules_enabled": 20,
        "files_checked": 180, "suppressions": 7, "findings": 0,
        "ok": True,
    }
    _assert_headline(bench._compact_line(p))


def test_bench_diff_gates_lint_schema_drift(tmp_path, capsys):
    """tools/bench_diff.py refuses (exit 2) to compare payloads whose
    lint blocks carry different lint_schema_versions — same discipline
    as the telemetry and fleet schema gates."""
    from tools import bench_diff
    base = {"metric": "m", "value": 1.0, "platform": "cpu",
            "telemetry_schema_version": 1,
            "extra": {"lint": {"lint_schema_version": 1,
                               "rules_enabled": 20, "files_checked": 180,
                               "suppressions": 7, "findings": 0,
                               "ok": True}}}
    drift = json.loads(json.dumps(base))
    drift["extra"]["lint"]["lint_schema_version"] += 1
    a, b = tmp_path / "a.json", tmp_path / "b.json"
    a.write_text(json.dumps(base))
    b.write_text(json.dumps(drift))
    rc = bench_diff.main([str(a), str(b), "--quiet"])
    out = capsys.readouterr().out
    assert rc == 2
    assert "lint_schema_drift" in out
    # same lint schema compares fine
    b.write_text(json.dumps(base))
    assert bench_diff.main([str(a), str(b), "--quiet"]) == 0


# ----------------------------------------------------------------------
# the `multiproc` block schema (ISSUE 19): pod/RPC config always real,
# recovery costs (coordinator_reinit_ms, sigkill_recover_ms) null unless
# THIS process actually went through a reshard — an in-process bench
# can't pass off "never killed anything" as "0 ms recovery"
# ----------------------------------------------------------------------

_MULTIPROC_KEYS = {
    "multiproc_schema_version", "procs", "world_size", "rpc_retries",
    "rpc_timeout_s", "coordinator_reinit_ms", "sigkill_recover_ms",
}


def test_multiproc_block_schema_is_stable(monkeypatch):
    monkeypatch.delenv("MXTPU_NUM_PROCESSES", raising=False)
    monkeypatch.delenv("MXTPU_RPC_RETRIES", raising=False)
    blk = bench._bench_multiproc()
    assert set(blk) - {"note"} == _MULTIPROC_KEYS
    assert blk["multiproc_schema_version"] == bench.MULTIPROC_SCHEMA_VERSION
    assert blk["procs"] == 1 and blk["world_size"] == 1
    assert blk["rpc_retries"] == 2 and blk["rpc_timeout_s"] == 5.0
    assert json.loads(json.dumps(blk)) == blk


def test_bench_multiproc_single_process_is_nulls_not_zeros(monkeypatch):
    """bench.py's multiproc block in one process: nothing was killed and
    nothing re-initialized, so the recovery costs are null — the
    correctness evidence lives in the real-process chaos suite
    (python -m mxnet_tpu.testing.chaos procs)."""
    monkeypatch.delenv("MXTPU_NUM_PROCESSES", raising=False)
    blk = bench._bench_multiproc()
    assert blk["coordinator_reinit_ms"] is None
    assert blk["sigkill_recover_ms"] is None
    assert "note" in blk and "testing.chaos procs" in blk["note"]


def test_multiproc_compact_keys_surface_when_measured():
    """The generic extras sweep surfaces the block's scalars as
    multiproc.<key> once measured; nulls never reach the headline."""
    p = _success_payload()
    p["extra"]["multiproc"] = {
        "multiproc_schema_version": bench.MULTIPROC_SCHEMA_VERSION,
        "procs": 4, "world_size": 4, "rpc_retries": 2,
        "rpc_timeout_s": 5.0,
        "coordinator_reinit_ms": 21.9, "sigkill_recover_ms": 830.0}
    obj = _assert_headline(bench._compact_line(p))
    assert obj["multiproc.coordinator_reinit_ms"] == 21.9
    assert obj["multiproc.sigkill_recover_ms"] == 830.0
    p["extra"]["multiproc"]["coordinator_reinit_ms"] = None
    p["extra"]["multiproc"]["sigkill_recover_ms"] = None
    obj = json.loads(bench._compact_line(p))
    assert "multiproc.coordinator_reinit_ms" not in obj
    assert "multiproc.sigkill_recover_ms" not in obj


def test_bench_diff_gates_multiproc_schema_drift(tmp_path, capsys):
    """tools/bench_diff.py refuses (exit 2) to compare payloads whose
    multiproc blocks carry different schema versions, and never treats
    the block's config keys (procs/world_size/rpc_retries) as
    metrics."""
    from tools import bench_diff
    blk = {"multiproc_schema_version": 1, "procs": 4, "world_size": 4,
           "rpc_retries": 2, "rpc_timeout_s": 5.0,
           "coordinator_reinit_ms": 21.9, "sigkill_recover_ms": None}
    base = {"metric": "m", "value": 1.0, "platform": "cpu",
            "telemetry_schema_version": 1,
            "extra": {"multiproc": blk}}
    drift = json.loads(json.dumps(base))
    drift["extra"]["multiproc"]["multiproc_schema_version"] += 1
    a, b = tmp_path / "a.json", tmp_path / "b.json"
    a.write_text(json.dumps(base))
    b.write_text(json.dumps(drift))
    rc = bench_diff.main([str(a), str(b), "--quiet"])
    out = capsys.readouterr().out
    assert rc == 2
    assert "multiproc_schema_drift" in out
    # same schema compares fine, and config keys are skipped: only the
    # measured *_ms field (direction "down") is a comparable metric
    flat = bench_diff.flatten(base)
    assert "extra.multiproc.procs" not in flat
    assert "extra.multiproc.world_size" not in flat
    assert "extra.multiproc.rpc_retries" not in flat
    assert "extra.multiproc.coordinator_reinit_ms" in flat
    assert bench_diff.direction(
        "extra.multiproc.coordinator_reinit_ms") == "down"
    b.write_text(json.dumps(base))
    assert bench_diff.main([str(a), str(b), "--quiet"]) == 0


# ----------------------------------------------------------------------
# the `quant` block schema (ISSUE 20): env-knob config + the fp8-KV
# capacity arithmetic always real; device-measured fields (decode
# drift, quantized-train MFU) null unless THIS run measured them
# ----------------------------------------------------------------------

_QUANT_KEYS = {
    "quant_schema_version", "compute_dtype", "kv_dtype",
    "kv_capacity_ratio", "kv_decode_drift", "quant_train_mfu",
}


def test_quant_block_schema_is_stable(monkeypatch):
    monkeypatch.delenv("MXTPU_COMPUTE_DTYPE", raising=False)
    monkeypatch.delenv("MXTPU_KV_DTYPE", raising=False)
    blk = bench._bench_quant()
    assert set(blk) - {"note"} == _QUANT_KEYS
    assert blk["quant_schema_version"] == bench.QUANT_SCHEMA_VERSION
    assert blk["compute_dtype"] == "fp32"
    assert blk["kv_dtype"] == "fp32"
    # the headline capacity claim: >= 2x blocks at equal pool bytes,
    # fp8 scale-row overhead included (pure arithmetic, real on CPU)
    assert blk["kv_capacity_ratio"] >= 2.0
    assert json.loads(json.dumps(blk)) == blk


def test_quant_block_unmeasured_is_nulls_not_zeros(monkeypatch):
    """An in-process CPU bench never ran a fp8-KV serving drift check
    or a quantized TPU training step — those fields are null, with the
    note pointing at the runs that measure them."""
    monkeypatch.delenv("MXTPU_COMPUTE_DTYPE", raising=False)
    monkeypatch.delenv("MXTPU_KV_DTYPE", raising=False)
    blk = bench._bench_quant()
    assert blk["kv_decode_drift"] is None
    assert blk["quant_train_mfu"] is None
    assert "note" in blk and "--kv-dtype fp8" in blk["note"]


def test_quant_block_reads_env_knobs(monkeypatch):
    monkeypatch.setenv("MXTPU_COMPUTE_DTYPE", "int8")
    monkeypatch.setenv("MXTPU_KV_DTYPE", "fp8")
    blk = bench._bench_quant()
    assert blk["compute_dtype"] == "int8"
    assert blk["kv_dtype"] == "fp8"


def test_quant_compact_keys_surface_when_measured():
    """The generic extras sweep surfaces the block's scalars as
    quant.<key> once measured; nulls never reach the headline."""
    p = _success_payload()
    p["extra"]["quant"] = {
        "quant_schema_version": bench.QUANT_SCHEMA_VERSION,
        "compute_dtype": "fp8", "kv_dtype": "fp8",
        "kv_capacity_ratio": 3.2, "kv_decode_drift": 0.005,
        "quant_train_mfu": 0.31}
    obj = _assert_headline(bench._compact_line(p))
    assert obj["quant.kv_capacity_ratio"] == 3.2
    assert obj["quant.kv_decode_drift"] == 0.005
    assert obj["quant.quant_train_mfu"] == 0.31
    p["extra"]["quant"]["kv_decode_drift"] = None
    p["extra"]["quant"]["quant_train_mfu"] = None
    obj = json.loads(bench._compact_line(p))
    assert "quant.kv_decode_drift" not in obj
    assert "quant.quant_train_mfu" not in obj


def test_bench_diff_gates_quant_schema_drift(tmp_path, capsys):
    """tools/bench_diff.py refuses (exit 2) to compare payloads whose
    quant blocks carry different schema versions; config strings never
    compare, kv_capacity_ratio gates upward and kv_decode_drift
    downward."""
    from tools import bench_diff
    blk = {"quant_schema_version": 1, "compute_dtype": "fp8",
           "kv_dtype": "fp8", "kv_capacity_ratio": 3.2,
           "kv_decode_drift": 0.005, "quant_train_mfu": None}
    base = {"metric": "m", "value": 1.0, "platform": "cpu",
            "telemetry_schema_version": 1,
            "extra": {"quant": blk}}
    drift = json.loads(json.dumps(base))
    drift["extra"]["quant"]["quant_schema_version"] += 1
    a, b = tmp_path / "a.json", tmp_path / "b.json"
    a.write_text(json.dumps(base))
    b.write_text(json.dumps(drift))
    rc = bench_diff.main([str(a), str(b), "--quiet"])
    out = capsys.readouterr().out
    assert rc == 2
    assert "quant_schema_drift" in out
    flat = bench_diff.flatten(base)
    assert "extra.quant.quant_schema_version" not in flat
    assert "extra.quant.kv_capacity_ratio" in flat
    assert bench_diff.direction("extra.quant.kv_capacity_ratio") == "up"
    assert bench_diff.direction("extra.quant.kv_decode_drift") == "down"
    b.write_text(json.dumps(base))
    assert bench_diff.main([str(a), str(b), "--quiet"]) == 0
