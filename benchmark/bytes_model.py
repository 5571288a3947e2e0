"""Bytes a reply cannot do without, from the data's own shapes.

For each triple pattern of the query: a pattern with a constant object (a
class, a department) needs that constant's index list once, one id a member;
any other pattern needs its predicate's edges once, two ids an edge. The
reply's rows are written once on the device and read once to the host, one id
a cell. Ids are 4 bytes on the device. Counts come from the plain
reference's own pass over the generated triples, not from the program's
store."""

from __future__ import annotations

ID_BYTES = 4


def query_bytes(ref, text: str, reply_rows: int) -> int:
    select, patterns = ref.resolved(text)
    total = 0
    for s, p, o in patterns:
        if not isinstance(o, str):
            total += ref.const_count(p, o) * ID_BYTES
        else:
            total += ref.edge_count(p) * 2 * ID_BYTES
    return total + 2 * reply_rows * len(select) * ID_BYTES
