"""Native codec differential suite: _fastcodec.parse_pack must agree with
the pure-Python json_codec.loads → pack path on every input — same columns,
same values, same rejections."""
import json
import math
import os
import random

import numpy as np
import pytest

import crdt_graph_tpu as crdt
from crdt_graph_tpu import native
from crdt_graph_tpu.codec import json_codec, packed
from crdt_graph_tpu.core import operation as op_mod

pytestmark = pytest.mark.skipif(not native.available(),
                                reason="no C++ toolchain")


def assert_same(payload, max_depth=16):
    want = packed.pack(json_codec.loads(payload), max_depth=max_depth)
    got = native.parse_pack(payload, max_depth=max_depth)
    assert got.num_ops == want.num_ops
    for f in ("kind", "ts", "parent_ts", "anchor_ts", "depth", "paths",
              "value_ref", "pos", "parent_pos", "anchor_pos", "target_pos"):
        np.testing.assert_array_equal(getattr(got, f), getattr(want, f), f)
    assert got.values == want.values
    return got


def test_golden_fixtures():
    # the JsonTest.elm shapes: add, del, batch
    assert_same('{"op":"add","path":[0,1],"ts":2,"val":"a"}')
    assert_same('{"op":"del","path":[1,2,3]}')
    assert_same('{"op":"batch","ops":['
                '{"op":"add","path":[0],"ts":1,"val":"x"},'
                '{"op":"del","path":[1]}]}')


def test_nested_batches_flatten_in_order():
    assert_same('{"op":"batch","ops":[{"op":"batch","ops":['
                '{"op":"add","path":[0],"ts":1,"val":1}]},'
                '{"op":"add","path":[1],"ts":2,"val":2},'
                '{"op":"batch","ops":[]}]}')


def test_unknown_tag_is_noop():
    got = assert_same('{"op":"mystery","path":[1]}')
    assert got.num_ops == 0
    # a NON-STRING tag is also just unknown (fuzz find, r4): Python
    # compares obj["op"] to the known tags and falls through
    for doc in ('{"op":[42949672967297]}', '{"op":null}', '{"op":7}',
                '{"op":{"x":1},"path":[0]}'):
        assert assert_same(doc).num_ops == 0
    assert_same('{"op":"batch","ops":[{"op":"future","x":[{"y":1}]},'
                '{"op":"add","path":[0],"ts":5,"val":null}]}')


def test_value_payload_types():
    vals = ["str", "", "unié中😀", 0, -5, 2**40, 1.5,
            -0.25, 1e10, True, False, None, [1, [2, "x"]],
            {"k": {"n": None, "l": [1.0]}}, "esc\"\\\n\t/"]
    ops = crdt.Batch(tuple(crdt.Add(i + 1, (i,), v)
                           for i, v in enumerate(vals)))
    payload = json_codec.dumps(ops)
    got = assert_same(payload)
    assert got.values == list(vals)


def test_whitespace_tolerated():
    assert_same('  {  "op" : "add" , "path" : [ 0 , 1 ] , "ts" : 2 , '
                '"val" : { "a" : [ 1 , 2 ] } }  ')


def test_random_session_payloads():
    from test_merge_kernel import _random_session
    for seed in (31, 32):
        merged, ops = _random_session(seed, n_replicas=3, steps=80)
        payload = json_codec.dumps(op_mod.from_list(ops))
        assert_same(payload)


@pytest.mark.parametrize("bad", [
    '{"op":"add","path":[0]}',                   # missing ts/val
    '{"op":"add","ts":1,"val":1}',               # missing path
    '{"op":"add","path":[0],"ts":1.5,"val":1}',  # float ts
    '{"op":"add","path":[0.5],"ts":1,"val":1}',  # float path elem
    '{"op":"add","path":[0],"ts":true,"val":1}',  # bool ts
    '{"op":"del"}',                              # missing path
    '{"op":"batch"}',                            # missing ops
    '{"op":"batch","ops":{}}',                   # ops not a list
    '{"path":[0],"ts":1,"val":1}',               # missing tag
    '{"op":"add","path":[0],"ts":4611686018427387905,"val":1}',  # >= 2^62
    'noise',
    '{"op":"add","path":[0],"ts":1,"val":1} trailing',
])
def test_rejections_match_python(bad):
    with pytest.raises(ValueError):
        native.parse_pack(bad)
    with pytest.raises(ValueError):
        packed.pack(json_codec.loads(bad))


def test_merge_from_native_pack_matches_oracle():
    from crdt_graph_tpu.ops import merge, view
    from test_merge_kernel import _random_session
    merged, ops = _random_session(33, n_replicas=4, steps=100)
    payload = json_codec.dumps(op_mod.from_list(ops))
    p = packed.pack_json(payload)
    t = view.to_host(merge.materialize(p.arrays()))
    assert view.visible_values(t, p.values) == merged.visible_values()


def test_big_int_and_unicode_roundtrip():
    # int64 extremes inside protocol range and astral-plane text
    ts = (2**30 - 1) * 2**32 + 7
    payload = json.dumps({"op": "add", "path": [0], "ts": ts,
                          "val": "\U0001F680 ß"})
    got = assert_same(payload)
    assert got.ts[0] == ts


def test_duplicate_keys_last_wins_like_python():
    # duplicate "ops": only the last list contributes (json.loads semantics)
    assert_same('{"op":"batch","ops":['
                '{"op":"add","path":[0],"ts":1,"val":"A"}],'
                '"ops":[{"op":"add","path":[0],"ts":2,"val":"B"}]}')
    # duplicate "ts"/"val": last wins
    assert_same('{"op":"add","path":[0],"ts":1,"ts":2,'
                '"val":"x","val":"y"}')
    # tag flips after fields: final tag governs
    assert_same('{"op":"del","path":[3],"op":"add","ts":9,"val":1}'
                .replace('"op":"add","ts"', '"op":"add","path":[0],"ts"'))


@pytest.mark.parametrize("bad", [
    '{"op":"del","path":[01]}',                     # leading zero
    '{"op":"add","path":[0],"ts":1,"val":1.}',      # trailing dot
    '{"op":"add","path":[0],"ts":1,"val":.5}',      # leading dot
    '{"op":"add","path":[0],"ts":1,"val":1.0e}',    # empty exponent
    '{"op":"add","path":[0],"ts":1,"val":00}',      # leading zero int
    '{"op":"add","path":[0],"ts":01,"val":1}',      # leading zero ts
])
def test_number_grammar_rejections_match_python(bad):
    with pytest.raises(ValueError):
        native.parse_pack(bad)
    with pytest.raises(ValueError):
        packed.pack(json_codec.loads(bad))


def test_error_offsets_are_real():
    with pytest.raises(ValueError, match="offset (?!0\\b)"):
        native.parse_pack('{"op":"add","path":[0],"ts":1,"val":1} x')


def test_semantic_checks_wait_for_final_tag():
    # unknown tags tolerate arbitrary field contents (json.loads parses,
    # decode ignores) — native must accept these too
    got = assert_same('{"op":"mystery","ts":1.5,"path":{"x":1}}')
    assert got.num_ops == 0
    # del ignores a float ts field entirely
    assert_same('{"op":"del","path":[0],"ts":2.5}'
                .replace('"path":[0]', '"path":[7]')
                .replace('[7]', '[0]'))


def test_python_json_extensions_accepted():
    # json.loads accepts NaN/Infinity/-Infinity and lone surrogates in
    # value payloads; parity demands the native parser does too
    got = assert_same('{"op":"add","path":[0],"ts":1,'
                      '"val":[Infinity,-Infinity]}')
    assert got.values == [[float("inf"), float("-inf")]]
    got = native.parse_pack('{"op":"add","path":[0],"ts":1,"val":NaN}')
    assert math.isnan(got.values[0])
    assert_same('{"op":"add","path":[0],"ts":1,"val":"\\ud800"}')
    assert_same('{"op":"add","path":[0],"ts":1,"val":"\\ud800\\udc00x"}')


def test_deep_nesting_rejected_not_segfault():
    """Untrusted wire input with pathological nesting must fail the parse
    cleanly (Python's json raises RecursionError; the native parser raises
    ValueError) — never overflow the C stack.  Guards both the value_py
    payload path and the skip_value unknown-field path."""
    deep = "[" * 100_000 + "]" * 100_000
    for doc in ('{"op":"add","path":[0],"ts":1,"val":' + deep + "}",
                '{"op":"add","path":[0],"ts":1,"val":1,"x":' + deep + "}"):
        with pytest.raises(ValueError, match="nesting too deep"):
            native.parse_pack(doc)
    # sane nesting (well under the 512 cap) still parses
    ok = ('{"op":"add","path":[0],"ts":1,"val":'
          + "[" * 100 + "1" + "]" * 100 + "}")
    assert native.parse_pack(ok).num_ops == 1


# ===== egress: encode_pack (the parse_pack mirror) =======================

def _pyside_dumps(ops):
    return json_codec.dumps(op_mod.from_list(tuple(ops)))


def encode_both(ops, max_depth=16):
    p = packed.pack(ops, max_depth=max_depth)
    return native.encode_pack(p).decode(), _pyside_dumps(ops)


def test_encode_golden_fixtures_byte_exact():
    ops = [crdt.Add(2, (0, 1), "a"), crdt.Delete((1, 2, 3)),
           crdt.Add(1, (0,), "x")]
    got, want = encode_both(ops)
    assert got == want


def test_encode_value_payload_types_byte_exact():
    vals = ["str", "", "unié中😀", 0, -5, 2**40, 2**80, -2**90, 1.5,
            -0.25, -0.0, 1e10, 1e-12, float("inf"), float("-inf"),
            float("nan"), True, False, None, [1, [2, "x"], (3, 4)],
            {"k": {"n": None, "l": [1.0]}, "é": "☃"},
            {1: "a", 2.5: "b", True: "c", None: "d"},
            "esc\"\\\n\t/control\x01\x1f"]
    ops = [crdt.Add(i + 1, (i,), v) for i, v in enumerate(vals)]
    got, want = encode_both(ops)
    assert got == want
    # NaN breaks == on reparse; compare through repr of parsed trees
    assert repr(json.loads(got)) == repr(json.loads(want))


def test_encode_lone_surrogates_round_trip():
    # the parser admits lone surrogates (like json.loads); the encoder
    # must re-emit their \uD8xx escapes exactly like json.dumps
    payload = '{"op":"add","path":[0],"ts":1,"val":"hi\\ud800there"}'
    p = native.parse_pack(payload)
    assert native.encode_pack(p).decode() == \
        _pyside_dumps([json_codec.loads(payload)])


def test_encode_random_sessions_byte_exact():
    rng = random.Random(7)
    for seed in range(3):
        ops = []
        t = 1
        anchors = [0]
        for _ in range(300):
            if ops and rng.random() < 0.2:
                ops.append(crdt.Delete((rng.choice(anchors[1:] or [1]),)))
            else:
                a = rng.choice(anchors)
                ops.append(crdt.Add(t, (a,), rng.choice(
                    ["v%d" % t, t * 1.5, None, {"n": t}, ["l", t]])))
                anchors.append(t)
                t += 1
        got, want = encode_both(ops)
        assert got == want


def test_encode_start_slices_suffix():
    ops = [crdt.Add(i + 1, (i,), "v%d" % i) for i in range(10)]
    p = packed.pack(ops)
    got = native.encode_pack(p, start=6).decode()
    assert got == _pyside_dumps(ops[6:])


def test_encode_skips_padding_rows():
    ops = [crdt.Add(1, (0,), "a"), crdt.Add(2, (1,), "b")]
    p = packed.pack(ops, capacity=16)      # padded to 16 rows
    # num_ops bounds the scan, but even a raw full-capacity call must
    # skip KIND_PAD rows
    got = native.encode_pack(p).decode()
    assert got == _pyside_dumps(ops)


def test_encode_rejects_unencodable_value():
    class Opaque:
        pass
    p = packed.pack([crdt.Add(1, (0,), Opaque())])
    with pytest.raises(ValueError):
        native.encode_pack(p)


def test_parse_encode_round_trip_is_identity():
    payload = ('{"op":"batch","ops":['
               '{"op":"add","path":[0],"ts":1,"val":{"rich":[1,2.5,null]}},'
               '{"op":"add","path":[1],"ts":2,"val":"x"},'
               '{"op":"del","path":[1]}]}')
    p = native.parse_pack(payload)
    assert native.encode_pack(p).decode() == payload


def test_build_is_keyed_on_source_content(tmp_path, monkeypatch):
    """A copied tree's mtimes say nothing: the extension's file name
    carries the source's hash, so edited source never loads a stale
    build."""
    src = tmp_path / "fastcodec.cpp"
    src.write_bytes(open(native._SRC, "rb").read())
    monkeypatch.setattr(native, "_SRC", str(src))
    first = native._so_path()
    assert os.path.dirname(first) == str(tmp_path)
    assert native._so_path() == first
    src.write_bytes(src.read_bytes() + b"\n// edited\n")
    assert native._so_path() != first
