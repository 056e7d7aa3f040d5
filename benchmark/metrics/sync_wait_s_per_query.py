"""Task pump: thread-seconds a thread of the engine spent inside a host read,
per completed query: the ``sync:*`` regions (blocking and async alike) and
what is left of ``wait:harvest`` around them. Two map pumps wait at once, so
it can pass the query's wall seconds. It is one of the two places a pump's
wait for the device falls in; the other, a jit dispatch held while the
device's queue is full, is in no region and reads as ``op_host_s_per_query``
(or ``exchange_s_per_query``). Only the sum of the three is steady from run
to run while the device is the wall."""

from benchmark.rings import per_query, self_s


def read(facts: dict):
    return per_query(facts, lambda s: self_s(s, "sync", "wait:harvest"))
