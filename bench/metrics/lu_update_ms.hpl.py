"""Host time of the HPL driver's trailing updates per solve, in ms: the
``linalg.lu.update`` spans of ``repro.linalg.lu.lu_factor`` (per block step,
L21's plan, the emulated product, its copy to the host and the host
``-=``), summed over the window's host lines."""

SPANS = ("linalg.lu.update",)


def read(trace, counts):
    ns = sum(e.end - e.start for line in trace.host_events.values() for e in line
             if e.name in SPANS)
    if ns <= 0 or not counts.get("solves"):
        return None
    return ns / 1e6 / counts["solves"]
