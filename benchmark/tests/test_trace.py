"""The trace reducer: on a hand-made trace whose answers can be worked
out on paper, and on the start of a trace recorded on the v5e chip
(train-gpt2m, PR 25; the first 400 operations of each line)."""

import os

import numpy as np
import pytest

from benchmark.lib import kernels, trace

FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures", "trace_train_v5e.json")


def _line(events):
    names, starts, durs = zip(*events)
    return list(names), np.asarray(starts, float), np.asarray(durs, float)


def hand_made():
    """Two chips, a window of 1,000 us. Chip 0: a while of 400 us holding a
    fusion (100) and an all-reduce (50), then idle 300 us under
    bench:wait, then a copy of 200 us, then 100 us idle to the end.
    Chip 1: one fusion of 1,000 us."""
    us = 1_000.0
    w = "%while.1 = (s32[], f32[8]) while(%t), body=%b"
    f = "%fusion.2 = f32[8,128]{1,0:T(8,128)} fusion(f32[8,128] %p), kind=kLoop"
    ar = "%all-reduce.3 = f32[8]{0} all-reduce(f32[8] %x), replica_groups={}"
    c = "%copy.4 = bf16[2,4]{1,0:T(8,128)(2,1)} copy(bf16[2,4] %y)"
    return {
        "/device:TPU:0": {
            "XLA Ops": _line([(w, 0, 400 * us), (f, 10 * us, 100 * us),
                              (ar, 200 * us, 50 * us), (c, 700 * us, 200 * us)]),
            "XLA Modules": _line([("jit_step(123)", 0, 400 * us),
                                  ("jit_other(9)", 700 * us, 200 * us)]),
        },
        "/device:TPU:1": {"XLA Ops": _line([(f, 0, 1000 * us)])},
        "/host:CPU": {"python3": _line([
            ("bench:step", 0, 450 * us), ("bench:wait", 450 * us, 200 * us),
            ("bench:step", 650 * us, 350 * us)])},
    }


def test_hand_made_trace():
    s = trace.summarize(hand_made(), chips=2)
    assert s.window_s == pytest.approx(1e-3)
    assert s.busy_s == pytest.approx((600e-6 + 1000e-6) / 2)
    assert s.op_self_s["while.1"] == pytest.approx(250e-6 / 2)
    assert s.op_self_s["fusion.2"] == pytest.approx((100e-6 + 1000e-6) / 2)
    assert s.collective_exposed_s == pytest.approx(50e-6 / 2)
    assert s.module_seconds("jit_step") == pytest.approx([400e-6])
    assert s.module_seconds("jit_") == pytest.approx([400e-6, 200e-6])
    assert s.module_gaps("jit_") == pytest.approx([300e-6])
    gaps = dict(s.top_gaps(10))
    # 400-700 us: 50 under the first step, 200 under the wait, 50 under
    # the second step; 900-1000 us: under the second step
    assert gaps == {"bench:step": pytest.approx(200e-6),
                    "bench:wait": pytest.approx(200e-6)}
    assert s.top_ops(1)[0][0] == "fusion.2__fusion"


def test_names_shapes_and_nesting():
    hlo = ("%checkpoint.9 = (f32[2,128,1024,64]{3,2,1,0:T(8,128)}, f32[128,1024,64]"
           "{2,1,0:T(8,128)}, f32[128,1024,64]{2,1,0:T(8,128)}) custom-call(f32[1] %a)")
    assert trace.short_name(hlo) == "checkpoint.9"
    assert trace.opcode(hlo) == "custom-call"
    assert trace.result_shapes(hlo) == [
        ("f32", (2, 128, 1024, 64)), ("f32", (128, 1024, 64)), ("f32", (128, 1024, 64))]
    assert trace.opcode("%copy.1 = bf16[36,512]{1,0:T(8,128)(2,1)} copy(bf16[36,512] %g)") == "copy"
    selfs = trace.self_times(np.array([0.0, 10, 20, 100]), np.array([50.0, 5, 10, 10]))
    assert selfs.tolist() == [35.0, 5.0, 10.0, 10.0]
    s, e = trace.merged(np.array([0.0, 10, 100]), np.array([50.0, 5, 10]), 5, 105)
    assert s.tolist() == [5.0, 100.0] and e.tolist() == [50.0, 105.0]


def test_recorded_chip_trace():
    s = trace.summarize(trace.load_fixture(FIXTURE), chips=1)
    # eight 3-step dispatches of jit_epoch were traced, 849 ms each
    runs = s.module_seconds("jit_epoch")
    assert len(runs) == 8 and np.median(runs) == pytest.approx(0.84938, rel=1e-4)
    assert s.window_s == pytest.approx(6.824858, rel=1e-6)
    # only the first operations are kept: they lie in the first dispatch
    assert s.busy_s == pytest.approx(0.8493795, rel=1e-6)
    assert sum(s.op_self_s.values()) == pytest.approx(s.busy_s, rel=1e-9)
    shape, calls = kernels.flash_calls(s, backward=False)
    assert shape == (128, 1024, 64) and len(calls) == 8
    assert np.mean(calls) == pytest.approx(0.945e-3, rel=0.01)
    assert dict(s.top_gaps(3))["bench:trainer.step"] > 0
    gaps = s.module_gaps("jit_epoch")
    assert len(gaps) == 7 and np.median(gaps) == pytest.approx(3.760e-3, rel=1e-3)
