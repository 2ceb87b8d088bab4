"""``benchmark/scopes.py``'s reading of kernel names and of the xplane's
wire format, on strings and bytes made by hand (the reading of a scope
is the program's own, ``observability/names.scope_of``, held in tier-1)."""

import struct

from benchmark import scopes

def test_kernel_names_match_whole_words_longest_first():
    k = lambda *texts: scopes.kernel_of(texts)  # noqa: E731
    assert k("%flash_bwd_fused.3 = custom-call()") == "flash_bwd_fused"
    assert k("%custom-call.9", "jit(epoch)/attn_core/flash_fwd") == "flash_fwd"
    assert k("%my_flash_fwd_2 = fusion()") is None
    assert k("%fusion.284 = fusion()") is None


def _varint(n: int) -> bytes:
    out = b""
    while True:
        out += bytes([(n & 0x7F) | (0x80 if n > 0x7F else 0)])
        n >>= 7
        if not n:
            return out


def _field(no: int, payload) -> bytes:
    if isinstance(payload, int):
        return _varint(no << 3) + _varint(payload)
    return _varint(no << 3 | 2) + _varint(len(payload)) + payload


def test_metadata_statistics_are_read_from_the_wire_format(tmp_path):
    stat_meta = lambda sid, name: _field(5, _field(1, sid) + _field(  # noqa: E731
        2, _field(1, sid) + _field(2, name)))
    event_meta = _field(4, _field(1, 7) + _field(2, (
        _field(1, 7) + _field(2, b"%fusion.284 = f32[8] fusion()")
        + _field(5, _field(1, 1) + _field(5, b"jit(epoch)/mlp/mul"))
        + _field(5, _field(1, 2) + _varint(2 << 3 | 1) + struct.pack("<d", 2.5))
        + _field(5, _field(1, 3) + _field(7, 1)))))
    lines = _field(3, b"\x00" * 64)  # never parsed: skipped by its length
    plane = (_field(1, 1) + _field(2, b"/device:TPU:0") + lines + event_meta
             + stat_meta(1, b"tf_op") + stat_meta(2, b"flops")
             + stat_meta(3, b"category"))
    host = _field(1, 2) + _field(2, b"/host:CPU") + event_meta
    path = tmp_path / "hand.xplane.pb"
    path.write_bytes(_field(1, plane) + _field(1, host))
    assert scopes.metadata_stats(str(path)) == {"/device:TPU:0": {
        "%fusion.284 = f32[8] fusion()": {
            "tf_op": "jit(epoch)/mlp/mul", "flops": 2.5, "category": "tf_op"}}}
