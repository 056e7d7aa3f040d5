"""Operators: DECIMAL cells the host handled one by one in Python, per
completed query: the ``cells`` of the window's ``decimal`` events
(``obs.note_decimal_host_cells``: a wide sum or average rebuilt from its limbs
at ``_final_wide``'s capacity, a wide-decimal arithmetic or comparison table by
its dictionary's entries or distinct pairs), summed by ``obs.window_summary`` as
``wide_decimal_host_cells``. None on a program whose summary has no such
sum."""

from benchmark.rings import per_query


def read(facts: dict):
    try:
        return per_query(facts, lambda s: s["wide_decimal_host_cells"])
    except KeyError:
        return None
