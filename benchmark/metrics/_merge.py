"""Which device executables are the merge: every jitted program whose
name holds ``materialize`` (single-document, cross-document vmapped,
and ops-axis sharded merges)."""


def merge_ns(trace):
    return sum(v for k, v in trace["module_ns"].items()
               if "materialize" in k)
