"""Operators: groups the aggregates emitted, per completed query: the
``groups`` of the window's ``emit`` events (``obs.note_agg_emit``: one a state
an aggregate hands on at the end of its stream, partial, merge and final modes
alike, and one an intermediate passed through while skipping), summed by
``obs.window_summary`` as ``agg_groups``. The counts are those the aggregate
holds on the host from the reads it makes anyway; an emission no read has
settled counts as nothing. Query 65 reads three times its (store, item) pairs
(the partial sum, the final sum of stage 2 and of stage 4) plus the partial
and final averages' stores. None on a program whose summary has no such sum."""

from benchmark.rings import per_query


def read(facts: dict):
    try:
        return per_query(facts, lambda s: s["agg_groups"])
    except KeyError:
        return None
