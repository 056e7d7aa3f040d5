"""Entry layer: the responses' own ``wall_s`` (arrival at ``SqlServer.submit``
to its return) over the clients' latencies, summed over the window. The rest
is HTTP, JSON and the socket."""


def read(facts: dict):
    rs = [r for r in facts["records"]
          if r["ok"] and r.get("server_wall_s") is not None]
    client = sum(r["t1"] - r["t0"] for r in rs)
    if not rs or client <= 0:
        return None
    return 100.0 * sum(r["server_wall_s"] for r in rs) / client
