"""Share of the traced window's device busy time in collective ops, on
the first device (each busy instant given to the innermost op covering
it, as ``progtrace.busy_by`` does).

A collective is an op whose HLO name is ``all-gather``, ``all-reduce``,
``reduce-scatter``, ``all-to-all`` or ``collective-permute``, alone or in
its ``-start``/``-done`` form, or the TPU compiler's
``async-collective-start``/``-done`` (fusions that start and finish an
asynchronous collective, such as the all-gather of an FSDP weight shard),
with the op's number after a dot.  A traced four-chip granite step on a
2x2 v5e mesh shows ``all-gather``, ``all-reduce``, ``all-to-all``,
``collective-permute-start``/``-done`` and ``async-collective-start``/
``-done`` (``tests/data/progtrace_excerpt_v5e_mesh2x2.json``).  Only the
ops' own time counts: the transfer of an asynchronous collective that
overlaps compute between its start and its done is not in it."""

import re

from benchmarks.lms_bench import progtrace

COLLECTIVE = re.compile(r"(all-gather|all-reduce|reduce-scatter|all-to-all"
                        r"|collective-permute|async-collective)"
                        r"(-start|-done)?(\.\d+)?$")


def read(ctx):
    ev = progtrace.for_ctx(ctx)
    if not ev or not ev["ops"]:
        return None
    by = progtrace.busy_by(ev, lambda op: bool(COLLECTIVE.match(op[0])))
    total = sum(by.values())
    return by.get(True, 0.0) / total if total > 0 else None
