"""End to end, SQL cells: requests answered 200 and complete in the window over
the window's seconds (first POST to the last byte of the last reply), from the
client's side of the socket."""


def read(facts: dict):
    done = sum(1 for r in facts["records"] if r["ok"])
    return done / facts["window_s"] if done else None
