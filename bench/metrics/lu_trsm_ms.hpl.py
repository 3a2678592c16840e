"""Host time of the HPL driver's U12 triangular solves per solve, in ms: the
``linalg.lu.trsm`` spans of ``repro.linalg.lu.lu_factor`` (per block step,
U12 := L11^-1 A12 through ``blas3.trsm``), summed over the window's host
lines."""

SPANS = ("linalg.lu.trsm",)


def read(trace, counts):
    ns = sum(e.end - e.start for line in trace.host_events.values() for e in line
             if e.name in SPANS)
    if ns <= 0 or not counts.get("solves"):
        return None
    return ns / 1e6 / counts["solves"]
