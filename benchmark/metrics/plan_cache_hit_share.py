"""Entry layer: the share of the window's answers that the plan cache served,
from the ``cache_hit`` field of each response (``SqlServer.submit``'s record)."""


def read(facts: dict):
    hits = [r["cache_hit"] for r in facts["records"]
            if r["ok"] and r.get("cache_hit") is not None]
    if not hits:
        return None
    return 100.0 * sum(bool(h) for h in hits) / len(hits)
