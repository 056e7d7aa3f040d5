"""Operators: self seconds of the task pump's ``pump:batch`` spans, per
completed query: what a pump thread spends in the operator tree outside host
reads, harvests, queue waits and exchange I/O. That is the operators' host
work PLUS every jit dispatch of theirs, and a dispatch returns only when the
device's queue has room: while the device is the wall most of this number is
that wait (15 of 12-19 s a query on the v5e, PERF.md section 5), and it trades
seconds with ``sync_wait_s_per_query`` from run to run. It is host work alone,
the floor ``batch_query_s`` meets, only once the device stops being the wall."""

from benchmark.rings import per_query, self_s


def read(facts: dict):
    return per_query(facts, lambda s: self_s(s, "pump:batch"))
