"""Exchange layer: bytes of the ``map*.data`` files the map tasks wrote into
the query's own work directory (the driver owns it), per completed query."""


def read(facts: dict):
    sizes = [r["shuffle_bytes"] for r in facts["records"]
             if r["ok"] and r.get("shuffle_bytes") is not None]
    if not sizes:
        return None
    return sum(sizes) / len(sizes)
