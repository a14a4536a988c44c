"""Device milliseconds in the histogram kernels (``lgbm_hist_*`` events of the
trace) per full-data pass, over the traced trees.  Every histogram kernel
counts, also those of the speculative ramp and the leaf refit, which are not
full-data passes: the quotient is the kernel time one counted pass costs."""


def read(facts):
    secs, passes = facts.traced_kernel_s("lgbm_hist_"), facts.traced_passes()
    if secs is None or passes is None:
        return None
    return 1e3 * secs / passes
