"""Operators: rows of capacity the unique-build joins gathered their build
columns at, per completed query: the ``rows`` of every ``take`` event of the
window (``obs.note_join_take``: one per take of the join's output boundary, in
the BHJ driver, its fused stage twin and the star-join chain, a seed's and a
mispredict's repair included; the chain counts its width once a level), summed
by ``obs.window_summary`` as ``join_gather_rows``. Dense it reads probes x
batches x the batch's capacity (50.3 M a query in the batch cells, 46.1 M in
the SQL cell), compacted it reads the survivors' buckets. None on a program
whose summary has no such sum."""

from benchmark.rings import per_query


def read(facts: dict):
    try:
        return per_query(facts, lambda s: s["join_gather_rows"])
    except KeyError:
        return None
