"""The reduction from intervals to numbers: on hand-made intervals, and on a
few steps cut from a trace recorded on the chip (``fixtures/``)."""
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path[:0] = [BENCH, os.path.dirname(BENCH)]

import trace as tr                      # noqa: E402
from readers import collectives, device, kernels   # noqa: E402


def test_union_total_clip_subtract():
    assert tr.union([(5, 7), (0, 2), (1, 3), (3, 4), (9, 9)]) == \
        [(0, 4), (5, 7)]
    assert tr.total([(0, 4), (5, 7)]) == 6
    assert tr.clip([(0, 4), (5, 7), (8, 9)], 3, 6) == [(3, 4), (5, 6)]
    assert tr.subtract([(0, 10), (20, 30)], [(2, 4), (8, 22), (29, 40)]) == \
        [(0, 2), (4, 8), (22, 29)]
    assert tr.subtract([(0, 10)], []) == [(0, 10)]
    assert tr.subtract([(0, 10)], [(0, 10)]) == []


def _hand_made():
    """Four steps of 100 ns on device 0; in each, ops cover 90 ns.

    step at t:  [t, t+40) fusion.1   [t+40, t+50) mxtpu_flash_fwd.7
    [t+50, t+60) all-reduce-start.1 on the core's line
    [t+60, t+80) fusion.2   [t+80, t+90) all-reduce-done.1   idle [t+90, t+100)
    beside the core: all-reduce.1 runs [t+50, t+90)
    """
    ops, async_ops, modules = [], [], []
    for t in range(0, 400, 100):
        modules.append(("jit_train_step(1)", t, 100))
        ops += [("fusion.1", t, 40), ("mxtpu_flash_fwd.7", t + 40, 10),
                ("all-reduce-start.1", t + 50, 10), ("fusion.2", t + 60, 20),
                ("all-reduce-done.1", t + 80, 10)]
        async_ops.append(("all-reduce.1", t + 50, 40))
        # the tiny programs a step also runs are no steps
        modules.append(("jit__threefry_split(2)", t + 95, 1))
    host = [("bench.wait", 0, 195), ("bench.dispatch", 195, 205)]
    labels = {"fusion.1": "fusion.1 [fusion:Loop bf16[128,256,56,56]]"}
    return tr.Trace({0: {tr.MODULES: modules, tr.OPS: ops,
                         tr.ASYNC_OPS: async_ops}}, host, labels)


def test_window_leaves_out_first_and_last_step():
    assert _hand_made().window(0) == (100, 300, 2)


def test_busy_union_and_idle_share():
    busy, window, steps = _hand_made().busy(0)
    assert (busy, window, steps) == (180, 200, 2)


def test_kernel_time_by_name():
    t = _hand_made()
    assert t.op_time(0, "mxtpu_flash_fwd") == (20, 2)
    assert t.op_time(0, "fusion.2") == (40, 2)
    assert t.op_time(0, "no_such_kernel") == (0, 0)


def test_exposed_collective_arithmetic():
    # per step the collective runs 40 ns (50..90); fusion.2 hides 20 of it,
    # the start and done on the core's line are collective time themselves
    exposed, whole = _hand_made().exposed_collective(0)
    assert (exposed, whole) == (40, 80)


def test_top_ops_and_idle_gaps():
    t = _hand_made()
    top = t.top_ops(0, 2)
    assert top[0] == ["fusion.1 [fusion:Loop bf16[128,256,56,56]]", 80e-9]
    assert top[1][0].startswith("fusion.2")
    gaps = dict(t.idle_gaps(0))
    # idle 190..200 (5 in wait, 5 in dispatch) and 290..300 (dispatch)
    assert gaps == {"bench.dispatch": 15e-9, "bench.wait": 5e-9}


def test_short_form_of_an_event_name():
    name, hlo = tr.split_hlo(
        "%fusion.6 = f32[30522,768]{1,0:T(8,128)} fusion(f32[30522,768]"
        "{1,0:T(8,128)} %p), kind=kCustom, calls=%c")
    assert name == "fusion.6"
    assert tr.short_form(name, hlo) == \
        "fusion.6 [fusion:Custom f32[30522,768]]"
    name, hlo = tr.split_hlo(
        "%copy-start.4 = (s32[64,128]{1,0:T(8,128)S(1)}, s32[64,128]{1,0}, "
        "u32[]{:S(2)}) copy-start(s32[64,128]{1,0} %b)")
    assert tr.short_form(name, hlo) == "copy-start.4 [copy-start s32[64,128]]"
    name, hlo = tr.split_hlo(
        "%mxtpu_flash_fwd.12 = (bf16[1536,128,64]{2,1,0:T(8,128)(2,1)S(1)}, "
        "f32[1536,8,128]{2,1,0:T(8,128)}) custom-call(bf16[1536,128,64]"
        "{2,1,0:T(8,128)(2,1)} %bitcast.2323), "
        'custom_call_target="tpu_custom_call"')
    assert tr.short_form(name, hlo) == \
        "mxtpu_flash_fwd.12 [custom-call:tpu_custom_call bf16[1536,128,64]]"
    assert tr.split_hlo("jit_train_step(123)") == ("jit_train_step(123)", "")
    assert tr.short_form("x", "") == "x"


class _Ctx:
    def __init__(self, **kw):
        self.__dict__.update(kw)
        self.notes = {}

    def note(self, key, value):
        self.notes[key] = value


def test_readers_on_hand_made_intervals():
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    ctx = _Ctx(trace=_hand_made(), device_ids=[0], global_batch=8,
               flops_per_sample=1e3, peaks=peaks,
               sizes={"num_attention_heads": 1, "hidden_size": 64,
                      "dtype": "bfloat16"},
               traffic={"per_chip_batch": 8, "seq_len": 128})
    assert device.idle_pct(ctx) == pytest.approx(10.0)
    # 2 steps x 8 samples in 200 ns, 1e3 FLOPs each
    assert device.mfu_pct(ctx) == pytest.approx(
        100 * 1e3 * (16 / 200e-9) / 197e12)
    assert kernels.flash_fwd_ms(ctx) == pytest.approx(10e-6)
    flops, nbytes = kernels.flash_fwd_cost(ctx.sizes, ctx.traffic)
    least = max(flops / 197e12, nbytes / 819e9)
    assert kernels.flash_fwd_roofline(ctx) == pytest.approx(
        100 * least * 2 / 20e-9)
    assert ctx.notes["bound.kernel.flash_fwd_roofline"]["bound"] == "bytes"
    assert collectives.exposed_ms(ctx) == pytest.approx(20e-6)


def test_readers_return_nothing_without_a_trace_or_the_kernel():
    ctx = _Ctx(trace=None, device_ids=[0])
    assert device.idle_pct(ctx) is None and device.mfu_pct(ctx) is None
    assert kernels.flash_fwd_ms(ctx) is None
    assert collectives.exposed_ms(ctx) is None
    bare = tr.Trace({0: {tr.MODULES: [("m", 0, 10)] * 1,
                         tr.OPS: [("fusion", 0, 5)]}}, [], {})
    ctx = _Ctx(trace=bare, device_ids=[0])
    assert kernels.flash_fwd_ms(ctx) is None
    assert collectives.exposed_ms(ctx) is None


def test_json_round_trip(tmp_path):
    t = _hand_made()
    path = str(tmp_path / "t.json.gz")
    t.dump(path)
    back = tr.Trace.load(path)
    assert back.busy(0) == t.busy(0) and back.labels == t.labels
    assert back.idle_gaps(0) == t.idle_gaps(0)


FIXTURE = os.path.join(HERE, "fixtures",
                       "bert-base-fused-b128-s128.5steps.trace.json.gz")


def test_recorded_trace():
    """Five steps cut from a traced run of bert-base-fused-b128-s128 on a
    v5e (PR 24): the reduction finds the three whole steps in the middle,
    twelve flash-forward calls in each, a core that is busy nearly all the
    time, and the idle time split over the host's annotations."""
    t = tr.Trace.load(FIXTURE)
    lo, hi, steps = t.window(0)
    busy, window, _ = t.busy(0)
    assert steps == 3 and window == hi - lo
    assert 95e6 < window / steps < 115e6        # ~104 ms a step
    assert 0.98 < busy / window < 1.0
    ns, calls = t.op_time(0, kernels.FLASH_FWD)
    assert calls == 3 * 12 and 9e6 < ns / steps < 13e6
    assert t.exposed_collective(0) == (0, 0)    # one chip: no collective
    top = t.top_ops(0)
    assert len(top) == 10 and all(sec > 0 for _, sec in top)
    assert any(label.startswith("mxtpu_flash_fwd") or "fusion" in label
               for label, _ in top)
    assert sum(sec for _, sec in t.idle_gaps(0)) == pytest.approx(
        (window - busy) / 1e9)
