"""Task pump: how far a stage's partitions ran side by side: the
thread-seconds of the window's ``pump:partition`` spans over the mesh's width
times the seconds in which at least one of them was open
(``obs.window_summary``'s ``partition_pumps``: ``thread_s``, ``open_s``,
``width``). 1.0 where every stage's partitions ran at once from start to end,
``1 / width`` where they took turns. None on a program without the span, or
where no partition was pumped."""


def read(facts: dict):
    records = facts["records"]
    if not any(r["ok"] for r in records):
        return None
    try:
        from auron_tpu import obs

        if obs.mode() == obs.MODE_OFF:
            return None
        summary = obs.window_summary(records[0]["t0"], records[-1]["t1"])
        pumps = summary["partition_pumps"]
    except (ImportError, AttributeError, KeyError):
        return None
    if not summary["complete"] or not pumps["width"] or pumps["open_s"] <= 0:
        return None
    return pumps["thread_s"] / (pumps["width"] * pumps["open_s"])
