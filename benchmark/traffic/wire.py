"""Wire-JSON encoding of op logs, byte-equal to the program's
``json_codec.dumps(Batch(ops))`` (``{"op":"batch","ops":[...]}`` with
``separators=(",", ":")``).  The chain encoder formats columns in one
pass with no per-op objects; it replaces the 7.6 s of op objects and
``dumps`` that a 1M-op body costs through the program's codec."""
from __future__ import annotations

import json

import numpy as np

HEAD = b'{"op":"batch","ops":['
TAIL = b"]}"


def _val(v) -> str:
    return str(v) if isinstance(v, int) else json.dumps(v)


def encode_op(op) -> str:
    if op[0] == "add":
        _, path, ts, val = op
        return (f'{{"op":"add","path":[{",".join(map(str, path))}],'
                f'"ts":{ts},"val":{_val(val)}}}')
    return f'{{"op":"del","path":[{",".join(map(str, op[1]))}]}}'


def dumps_ops(ops) -> bytes:
    """Op tuples (``("add", path, ts, val)``, ``("del", path)``) → body."""
    return HEAD + ",".join(map(encode_op, ops)).encode() + TAIL


def dumps_chain(anchor: np.ndarray, ts: np.ndarray,
                val: np.ndarray) -> bytes:
    """Depth-1 adds given as integer columns → body."""
    fmt = '{{"op":"add","path":[{}],"ts":{},"val":{}}}'.format
    return HEAD + ",".join(map(fmt, anchor.tolist(), ts.tolist(),
                               val.tolist())).encode() + TAIL
