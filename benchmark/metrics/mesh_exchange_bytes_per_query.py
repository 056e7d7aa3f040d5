"""Exchange: bytes the mesh transport moved, per completed query: the ``bytes``
of the window's ``exchange:write`` spans whose ``mode`` is ``mesh`` (the rows
routed times the schema's row width, values and validity: what the
``all_to_all`` carries between the chips), summed by ``obs.window_summary`` as
``exchange_bytes``. A file exchange counts under its own mode and not here.
None on a program whose summary has no such sum."""

from benchmark.rings import per_query


def read(facts: dict):
    try:
        return per_query(facts, lambda s: s["exchange_bytes"].get("mesh", 0))
    except KeyError:
        return None
