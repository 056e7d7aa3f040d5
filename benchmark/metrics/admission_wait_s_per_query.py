"""Admission: seconds in ``serve:admit`` spans (``serve/admission.py``: a
request's wait for a concurrency slot and for memory headroom, its
``queue_wait_s``), per completed query. With as many sessions as slots it
reads next to nothing; more sessions than slots, or a pool past its memory
fraction, show here first. None on a program without the ``serve`` layer."""

from benchmark.rings import per_query


def read(facts: dict):
    try:
        return per_query(facts, lambda s: s["spans"]["serve:admit"]["total_s"])
    except KeyError:
        return None
