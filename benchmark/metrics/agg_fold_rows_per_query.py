"""Operators: rows of capacity the partial aggregate's grouped reduce ran
over, per completed query: the ``rows`` of every ``fold`` event of the window
(``obs.note_agg_fold``: one per PARTIAL raw fold of the deferred aggregate, a
mispredict's repair included), summed by ``obs.window_summary`` as
``agg_fold_rows``. The sort, the gathers and the scatter of the sort-segmented
reduce all scale with it: at a batch's capacity it reads batches x 4,194,304,
compacted it reads the live rows' buckets. None on a program whose summary has
no such sum."""

from benchmark.rings import per_query


def read(facts: dict):
    try:
        return per_query(facts, lambda s: s["agg_fold_rows"])
    except KeyError:
        return None
