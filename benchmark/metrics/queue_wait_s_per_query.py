"""Task pump: thread-seconds in ``wait:queue_put`` (a pump blocked on its full
queue) and ``wait:queue_get`` (the caller blocked on an empty one), per
completed query. With one driver and a map stage that is the whole query, the
caller's ``queue_get`` is the query's wall: the number shadows
``batch_query_s`` until tasks overlap or the caller has work of its own."""

from benchmark.rings import per_query, self_s


def read(facts: dict):
    return per_query(facts, lambda s: self_s(s, "wait:queue_put", "wait:queue_get"))
