"""Operators: rows the unique-build joins looked up BY A GATHER, per completed
query: the ``rows`` of the window's ``lookup`` events
(``obs.note_join_lookup``: one a probed batch a level, in the BHJ driver, for
its fused stage twin too, and in the star-join chain; ``rows`` is the width the
lookup ran at, the batch's capacity) whose ``kind`` is ``lut`` (one gathered
element a row) or ``search`` (the sorted words), summed by
``obs.window_summary`` as ``join_lookup_rows`` by kind. The kind ``compare`` (a
small build's live key list, no gather) is what the sum leaves out. With every
build on its LUT it reads probes x batches x the batch's capacity (50.3 M a query
in the batch cells, 46.1 M in the SQL cell). None on a program whose summary has
no such sum."""

from benchmark.rings import per_query


def read(facts: dict):
    try:
        return per_query(
            facts,
            lambda s: sum(rows for kind, rows in s["join_lookup_rows"].items()
                          if kind != "compare"))
    except KeyError:
        return None
