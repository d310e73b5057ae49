"""Per call: time a chip spends in a collective operation while no other
operation runs on it, mean over the chips. An operation is a collective by
its own name, not by an operand's."""

import re

from chipbench import trace_reduce as tr_

COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all|collective-broadcast"
)


def read(reading):
    tr = reading.trace
    if tr is None or not tr.calls:
        return None
    lo, hi = tr.window
    exposed = 0.0
    for d in tr.devices:
        ops = tr_.leaves([e for e in d.ops if e.end > lo and e.start < hi])
        mine = [bool(COLLECTIVE.search(tr_.short_name(e.name))) for e in ops]
        coll = tr_.union((e.start, e.end) for e, c in zip(ops, mine) if c)
        rest = tr_.union((e.start, e.end) for e, c in zip(ops, mine) if not c)
        exposed += tr_.length(tr_.subtract(coll, rest))
    return exposed / len(tr.devices) / len(tr.calls) / 1e6
