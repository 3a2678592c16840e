"""Device busy time outside Pallas kernel events, per call, in ms: the XLA
float64 stages around the fused kernel (scaling with the accurate mode's
bound GEMM, decompose_raw, the digit reconstruction) and the harness's
gather of the sampled rows."""


def read(trace, counts):
    if trace.busy_s <= 0 or not counts.get("calls"):
        return None
    return 1e3 * (trace.busy_s - trace.kernel_s) / counts["calls"]
