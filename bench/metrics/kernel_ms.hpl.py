"""Device time of Pallas kernel events per solve, in ms."""


def read(trace, counts):
    if trace.kernel_s <= 0 or not counts.get("solves"):
        return None
    return 1e3 * trace.kernel_s / counts["solves"]
