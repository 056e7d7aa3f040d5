"""Device: bytes the engine read back from the device, per completed query:
the ``bytes`` argument of every ``sync:*`` region (one per ``ArrayImpl._value``
read that the counters' hook saw, blocking or async)."""

from benchmark.rings import per_query


def read(facts: dict):
    return per_query(facts, lambda s: s["d2h_bytes"])
