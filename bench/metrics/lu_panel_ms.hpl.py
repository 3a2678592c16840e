"""Host time of the HPL driver's panel factorizations per solve, in ms: the
``linalg.lu.panel`` spans of ``repro.linalg.lu.lu_factor`` (per block step,
the column loop: pivot searches, row swaps, pivot-column scaling, rank-1
updates), summed over the window's host lines."""

SPANS = ("linalg.lu.panel",)


def read(trace, counts):
    ns = sum(e.end - e.start for line in trace.host_events.values() for e in line
             if e.name in SPANS)
    if ns <= 0 or not counts.get("solves"):
        return None
    return ns / 1e6 / counts["solves"]
