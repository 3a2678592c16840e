"""Device program executions per solve."""


def read(trace, counts):
    if trace.launches <= 0 or not counts.get("solves"):
        return None
    return trace.launches / counts["solves"]
