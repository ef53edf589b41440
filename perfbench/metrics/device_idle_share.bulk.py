"""device_idle_share.bulk: 100 x (1 - busy / window), busy being the union
of device operation intervals in the profiler trace of the window, in the
process that holds the chip (lib/trace.reduce)."""


def read(run):
    red = run.get("trace")
    if not red:
        return None
    return 100.0 * (1.0 - red["busy_s"] / red["window_s"])
