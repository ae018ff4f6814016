"""The paper's wait%: the share of the worker threads' time in the
window's drains not spent computing (``WaitStats``, host clock), in %."""


def read(run):
    c = run["counters"]
    total = c.get("nworkers", 0) * c.get("worker_elapsed_s", 0.0)
    if total <= 0:
        return None
    return 100.0 * max(0.0, 1.0 - c["worker_busy_s"] / total)
