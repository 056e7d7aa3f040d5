"""Planner + fusion: seconds in ``plan:task`` spans (``task_from_proto``:
proto to exec tree, column pruning, the fusion rewrite ``plan:fusion`` inside
it), per completed query; four tasks a query."""

from benchmark.rings import per_query


def read(facts: dict):
    return per_query(
        facts, lambda s: s["spans"].get("plan:task", {"total_s": 0.0})["total_s"])
