"""SQL front end: seconds in ``serve:plan`` spans (``SqlServer.plan``: the
text's digest and the plan-cache lookup; on a miss the ``sql:sql.parse``,
``sql:sql.bind`` and ``sql:sql.lower`` spans inside it), per completed query.
None on a program without the ``serve`` layer."""

from benchmark.rings import per_query


def read(facts: dict):
    try:
        return per_query(facts, lambda s: s["spans"]["serve:plan"]["total_s"])
    except KeyError:
        return None
