"""Device-busy milliseconds outside the histogram kernels, per traced tree:
gradients, quantisation, split scan, row update, leaf renewal, score update."""


def read(facts):
    k = facts.counters.get("traced_trees", 0)
    if facts.trace is None or k <= 0:
        return None
    hist = facts.traced_kernel_s("lgbm_hist_") or 0.0
    return 1e3 * (facts.trace.busy_s - hist) / k
