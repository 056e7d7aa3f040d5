"""Entry layer, SQL cells: the 95th percentile of POST-to-last-byte latency
over ALL requests of the window, failed ones with the time they took to fail
(linear interpolation between closest ranks, as numpy's default). The sample
count is on the evidence line ``latency_s``. A per-layer metric
(``sql_latency_p95_s.layer``): a closed loop that keeps the device busy is at
capacity, where the rate is the end-to-end metric and the tail swings with the
order in which the streams' requests meet (PERF.md section 2)."""


def read(facts: dict):
    xs = sorted(r["t1"] - r["t0"] for r in facts["records"])
    if not xs:
        return None
    k = (len(xs) - 1) * 0.95
    lo = int(k)
    hi = min(lo + 1, len(xs) - 1)
    return float(xs[lo] + (xs[hi] - xs[lo]) * (k - lo))
