"""End to end, batch cells: the window's seconds (first submit to last result,
each query ended by its last ``next_batch``) over the queries completed in it.
All the work over all the time: queries run back to back."""


def read(facts: dict):
    done = sum(1 for r in facts["records"] if r["ok"])
    return facts["window_s"] / done if done else None
