"""Serving: self seconds of ``serve:request`` (the HTTP handler from the
body's first byte read to the answer's last byte written, less everything
below), ``serve:collect`` (the result frames from the collect task's batches,
less the task's own spans and the waits for it) and ``serve:encode`` (cells to
JSON-safe values, ``json.dumps``), per completed query: what answering over
HTTP costs on top of running the query. None on a program without the
``serve`` layer."""

from benchmark.rings import per_query, self_s


def read(facts: dict):
    def pick(s: dict) -> float:
        if "serve:request" not in s["spans"]:
            raise KeyError("serve:request")
        return self_s(s, "serve:request", "serve:collect", "serve:encode")

    try:
        return per_query(facts, pick)
    except KeyError:
        return None
