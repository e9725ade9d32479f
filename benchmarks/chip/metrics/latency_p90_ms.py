"""The 90th percentile of every query's latency in the window, in
milliseconds, nearest rank above, as MLPerf SingleStream reports it.  A
query's latency runs from before its input is sent to its answer being
ready (or on the host)."""

Q = 90


def read(run):
    s = sorted(run.window.latencies)
    if not s:
        return None
    return 1e3 * s[min(len(s) - 1, max(0, -(-Q * len(s) // 100) - 1))]
