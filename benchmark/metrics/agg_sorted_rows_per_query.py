"""Operators: rows of capacity the aggregates' grouped reduces SORTED, per
completed query: the ``rows`` of the window's ``reduce`` events
(``obs.note_agg_reduce``: one a call of ``HashAggExec._group_reduce``, a fold,
a merge of staged state or a collision repair alike) whose ``how`` is ``sort``
or ``hostsort``, summed by ``obs.window_summary`` as ``agg_sorted_rows``; a
``mergepath`` reduce (two sorted runs merged by rank) sorts nothing, nor does
a fold the dense table takes, which leaves no ``reduce`` event at all: 0 where
the dense table takes every row. None on a program whose summary has no such
sum."""

from benchmark.rings import per_query


def read(facts: dict):
    try:
        return per_query(facts, lambda s: s["agg_sorted_rows"])
    except KeyError:
        return None
