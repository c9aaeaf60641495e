"""Device time of the collective operations in the window, per write
acked in it, averaged over the chips (profiler trace).  An operation
counts by its HLO opcode, read from the instruction text the trace
names it by (``%psum.6 = s32[] all-reduce(...)``), else by its name:
the opcodes below, and the ``-start`` and ``-done`` halves of each.
The sharded merge compiled for a v5e 2x2 at 2^20 rows holds
``all-gather``, ``all-reduce`` (named ``psum``, ``pmin``, ``pmax`` or
``all-reduce``) and ``collective-permute-start`` / ``-done``."""
import re

COLLECTIVES = ("all-gather", "all-reduce", "collective-permute",
               "reduce-scatter", "all-to-all")
_OPCODE = re.compile(r" ([a-z][a-z0-9\-]*)\(")
_NAME = re.compile(r"^%?([a-z][a-z0-9\-]*?)(\.\d+)?$")


def opcode(op_name):
    """The HLO opcode of one trace event's name."""
    if " = " in op_name:
        m = _OPCODE.search(op_name.split(" = ", 1)[1])
        return m.group(1) if m else ""
    m = _NAME.match(op_name.strip())
    return m.group(1) if m else ""


def is_collective(op_name):
    op = opcode(op_name)
    for suffix in ("-start", "-done"):
        if op.endswith(suffix):
            op = op[:-len(suffix)]
    return op in COLLECTIVES


def read(ctx):
    n = len(ctx.acked_writes)
    if ctx.trace is None or not n:
        return None
    ns = sum(v for k, v in ctx.trace["op_ns"].items() if is_collective(k))
    return ns / 1e6 / n if ns else None
