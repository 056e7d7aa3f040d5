"""What the readers of the program's rings share: the window's summary by
layer (``auron_tpu.obs.window_summary``, whose clock is that of
``records[i]["t0"/"t1"]``), one picked number of it, over the queries
completed. Thread-seconds, not wall: two map pumps run at once."""


def per_query(facts: dict, pick):
    """``pick(summary)`` over the queries completed in ``[records[0].t0,
    records[-1].t1]``. None where the program has no ``window_summary`` (an
    older program), the recorder is off, nothing completed, or a ring of the
    window wrapped (``complete`` false: the sums are then a lower bound)."""
    records = facts["records"]
    done = sum(1 for r in records if r["ok"])
    if not done:
        return None
    try:
        from auron_tpu import obs

        if obs.mode() == obs.MODE_OFF:
            return None
        summary = obs.window_summary(records[0]["t0"], records[-1]["t1"])
    except (ImportError, AttributeError):
        return None
    if not summary["complete"]:
        return None
    return pick(summary) / done


def self_s(summary: dict, *names: str) -> float:
    """Summed self seconds of the named spans (``<layer>:<name>``) and whole
    layers (``<layer>``); one that never ran counts as zero."""
    return sum(summary["spans" if ":" in n else "layers"]
               .get(n, {"self_s": 0.0})["self_s"] for n in names)
