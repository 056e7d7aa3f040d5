"""Operators: rows of capacity the program sorted, per completed query: the
``rows`` of the window's ``sort`` events (``obs.note_sort``: a plan's Sort
in ``exec/sort_exec.py``, a join's sorted build in ``prepare_build``, an
aggregate's order from the host's lexsort), on the host and on the device
alike, summed by ``obs.window_summary`` as ``sort_rows``. In query 93 the two
Sorts under the sort-merge join are what it would read: both are dropped
(the build side's always, the probe side's where the task's plan proves its
order dies) and the build is a hash map, so it reads what is left. None on a
program whose summary has no such sum."""

from benchmark.rings import per_query


def read(facts: dict):
    try:
        return per_query(facts, lambda s: sum(
            rows for by_substrate in s["sort_rows"].values()
            for rows in by_substrate.values()))
    except KeyError:
        return None
