"""Task pump: over the window's stages (``pump:stage`` spans), the least
number of distinct devices a stage's partitions' inputs lay on
(``obs.window_summary``'s ``stage_devices_min``): the mesh's width where every
partition of every stage scans batches on a chip of its own, 1 where a stage's
partitions all read from one chip, 0 where their inputs lay on several at once
(replicated after an exchange: ROADMAP R-a3's fault). Not per query: a
minimum. None on a program without the span, or where no stage began."""


def read(facts: dict):
    records = facts["records"]
    if not any(r["ok"] for r in records):
        return None
    try:
        from auron_tpu import obs

        if obs.mode() == obs.MODE_OFF:
            return None
        summary = obs.window_summary(records[0]["t0"], records[-1]["t1"])
        return summary["stage_devices_min"] if summary["complete"] else None
    except (ImportError, AttributeError, KeyError):
        return None
