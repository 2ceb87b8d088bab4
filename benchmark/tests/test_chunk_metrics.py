"""The two readers of the decode chunk's own accounting (``emitted`` and
``slot_steps`` on the program's decode_chunk spans), on hand-made spans
whose answers can be worked out on paper."""

import pytest

from benchmark.lib import harness


def chunk(ts_ms, dur_ms, rids, emitted=None, chunk=8):
    args = {"chunk": chunk, "active": len(rids), "rids": rids, "barrier": "d2h"}
    if emitted is not None:
        args.update(emitted=emitted, slot_steps=len(rids) * chunk)
    return {"kind": "span", "name": "decode_chunk", "cat": "dispatch",
            "ts_us": ts_ms * 1e3, "dur_us": dur_ms * 1e3, "args": args}


def prefill(ts_ms, dur_ms, rids):
    return {"kind": "span", "name": "prefill", "cat": "dispatch",
            "ts_us": ts_ms * 1e3, "dur_us": dur_ms * 1e3,
            "args": {"bucket": 64, "admitted": len(rids), "rids": rids}}


def read(name, spans):
    run = harness.Run("serve-gpt2l-chat", {}, {}, 1, {}, spans=spans)
    return harness.metric_reader(name)(run)


def test_a_request_that_ends_at_step_5_of_a_chunk_of_8():
    # two chunks of 8 steps, two slots: request 1 runs both chunks through,
    # request 2 ends at step 5 of the second and rides 3 steps masked.
    spans = [
        prefill(0, 50, [1, 2]),
        chunk(100, 400, [1, 2], [8, 8]),
        chunk(600, 400, [1, 2], [8, 5]),
    ]
    # 29 tokens out of 32 slot-steps: 3/32 wasted
    assert read("chunk_tail_waste_pct.chat", spans) == pytest.approx(
        100 * 3 / 32)
    # each request saw deliveries at 500 ms and at 1000 ms: one gap of 500
    assert read("delivery_gap_p50_ms.chat", spans) == pytest.approx(500.0)


def test_a_request_absent_from_one_chunk():
    # request 7 is delivered to at 300, 700 and 1500 ms; request 9 only in
    # the chunks that end at 700 and 1100 (absent from the first, gone
    # from the last). A chunk in which a resident request got nothing
    # (emitted 0) is no delivery to it.
    spans = [
        chunk(100, 200, [7], [8]),
        chunk(400, 300, [7, 9], [8, 8]),
        chunk(800, 300, [7, 9], [0, 8]),
        chunk(1200, 300, [7], [4]),
    ]
    # request 7: 300 -> 700 -> 1500 (the 1100 chunk gave it nothing): 400, 800
    # request 9: 700 -> 1100: 400.  median of (400, 800, 400) = 400
    assert read("delivery_gap_p50_ms.chat", spans) == pytest.approx(400.0)
    # 36 tokens out of 6 x 8 = 48 slot-steps
    assert read("chunk_tail_waste_pct.chat", spans) == pytest.approx(25.0)


@pytest.mark.parametrize(
    "name", ["chunk_tail_waste_pct.chat", "delivery_gap_p50_ms.chat"])
def test_none_without_the_new_arguments(name):
    old = [chunk(100, 400, [1, 2]), chunk(600, 400, [1, 2])]
    assert read(name, old) is None
    assert read(name, old + [chunk(1100, 400, [1], [8])]) is None
    assert read(name, [prefill(0, 50, [1])]) is None
    assert read(name, []) is None


def test_one_delivery_is_no_gap():
    assert read("delivery_gap_p50_ms.chat", [chunk(0, 100, [1], [8])]) is None
    assert read("chunk_tail_waste_pct.chat", [chunk(0, 100, [1], [8])]) == 0.0


def test_the_manifest_lists_both_under_the_scheduler():
    got = {m["name"]: m for m in harness.manifest()["per_layer"]}
    for name in ("chunk_tail_waste_pct.chat", "delivery_gap_p50_ms.chat"):
        m = got[name]
        assert (m["layer"], m["source"], m["moves"], m["workloads"],
                m["better"]) == (
            "scheduler", "program_span", "tpot_p50_ms", ["serve-gpt2l-chat"],
            "lower")
