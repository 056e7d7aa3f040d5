"""Entry: self seconds of the ``entry:*`` spans (``bridge/api.py``: proto
decode and resources in ``call_native``, the Arrow materialisation in
``next_batch``, ``finalize_native``), per completed query; the queue wait,
the plan and the device reads inside them are taken out."""

from benchmark.rings import per_query, self_s


def read(facts: dict):
    return per_query(facts, lambda s: self_s(s, "entry"))
