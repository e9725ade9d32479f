"""Images completed per second, end to end: every image answered in the
window over the whole window, from the first query sent to the last
answer ready."""


def read(run):
    if run.window.seconds <= 0:
        return None
    return run.window.images / run.window.seconds
