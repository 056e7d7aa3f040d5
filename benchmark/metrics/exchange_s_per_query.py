"""Exchange: self seconds of ``exchange:write`` (repartition, compression,
file write, push) and ``exchange:read`` (block decode) spans, per completed
query; the device reads inside the repartition are taken out, its jit
dispatches are not. While the device is the wall a dispatch waits for room in
the device's queue, and that wait is in here: read the number beside
``op_host_s_per_query`` and ``sync_wait_s_per_query``, whose sum with it is
the pumps' thread-seconds and holds steady while the three trade seconds."""

from benchmark.rings import per_query, self_s


def read(facts: dict):
    return per_query(facts, lambda s: self_s(s, "exchange:write", "exchange:read"))
