"""verified_sigs_per_s: signatures answered correctly over the whole
window's wall time (host clock), from the first call's start to the end
of the last call."""


def read(run):
    if "lanes_correct" not in run:
        return None
    return run["lanes_correct"] / run["window_s"]
