"""Host time of the HPL solve's epilogue per solve, in ms: the
``linalg.lu.solve`` spans (the two triangular sweeps of each LU solve) and
the ``linalg.refine.residual`` spans (each ``b - A x`` product of the
refinement) of ``repro.linalg.solve.refine_solve``, summed over the
window's host lines."""

SPANS = ("linalg.lu.solve", "linalg.refine.residual")


def read(trace, counts):
    ns = sum(e.end - e.start for line in trace.host_events.values() for e in line
             if e.name in SPANS)
    if ns <= 0 or not counts.get("solves"):
        return None
    return ns / 1e6 / counts["solves"]
