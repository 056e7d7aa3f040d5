"""Operators: rows the unique-build joins looked up in a hash map, per
completed query: the ``rows`` of the window's ``lookup`` events
(``obs.note_join_lookup``: one a probed batch a level; ``rows`` is the width
the lookup ran at, the batch's capacity) whose ``kind`` is ``hash``
(``ops/hashmap.py``: a one-word build too wide for a LUT or a key list),
summed by ``obs.window_summary`` as ``join_lookup_rows["hash"]``. Query 93
probes every fact row once: about 23 M a query. 0 where every such build met
a duplicate and took its sorted path; None on a program whose summary has no
``join_hash_build`` (one without the hash map)."""

from benchmark.rings import per_query


def _hash_rows(summary: dict) -> int:
    if "join_hash_build" not in summary:
        raise KeyError("join_hash_build")
    return summary["join_lookup_rows"].get("hash", 0)


def read(facts: dict):
    try:
        return per_query(facts, _hash_rows)
    except KeyError:
        return None
