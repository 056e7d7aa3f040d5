"""Task pump: blocking device-to-host reads (``EngineCounters.syncs``, the
program's own counter) over the window, per completed query."""


def read(facts: dict):
    done = sum(1 for r in facts["records"] if r["ok"])
    if facts["host_syncs"] is None or not done:
        return None
    return facts["host_syncs"] / done
