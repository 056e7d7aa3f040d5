"""SQL front end: the share of the window's ``serve:plan`` spans whose
``cache_hit`` argument is true (``obs.window_summary``'s ``plan_cache_hits``
over hits and misses), in percent. A stream that replays its texts reads 100;
a change that breaks the digest or the cache's key reads 0 and pays parse,
bind and lower on every request. None on a program whose summary has no such
counts, or where no plan was looked up."""

from benchmark.rings import per_query


def read(facts: dict):
    try:
        # both per completed query: the ratio is that of the window's counts
        hits = per_query(facts, lambda s: s["plan_cache_hits"])
        misses = per_query(facts, lambda s: s["plan_cache_misses"])
    except KeyError:
        return None
    if hits is None or not hits + misses:
        return None
    return 100.0 * hits / (hits + misses)
