"""Share of the traced window in which no operation ran on the chip
(1 - busy union / window), in %."""


def read(run):
    trace = run.get("trace")
    if not trace or trace["window_s"] <= 0 or trace["n_chips"] == 0:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
