"""Share of an untraced window in which no operation ran on the device, in
percent, for one query at a time.  Layer: the device.

Under the profiler the host path of a query is several times slower than
without it (the runtime records each chunk of the input's host-side
relayout), so the traced window's own idle share mostly measures the
profiler.  The device's work per query is not slowed: so this is
1 - (device busy time per query, from the trace) / (time per query in the
untraced window).
"""


def read(run):
    if run.trace.busy_s <= 0 or run.traced.attempted == 0 \
            or run.window.attempted == 0:
        return None
    busy_per_query = run.trace.busy_s / run.traced.attempted
    period = run.window.seconds / run.window.attempted
    return 100.0 * (1.0 - busy_per_query / period)
