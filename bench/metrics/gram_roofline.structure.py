"""Share of its roofline that the packed sign Gram kernel reaches in the
structure loop, in %: one (d, d) Gram of n samples per structure, its
least time over the kernel's summed device time."""
from bench import roofline, trace

KERNELS = r"^(sign_corr_packed|sign_corr)(\.\d+)?$"


def read(ctx):
    c, cfg, peak = ctx["counters"], ctx["config"], ctx["peak"]
    ns = trace.op_ns(ctx["trace"], KERNELS)
    if not c.get("structures") or ns <= 0 or peak is None:
        return None
    t, _ = roofline.gram_least_seconds(cfg["n"], cfg["d"], cfg["method"],
                                       cfg["wire"], peak)
    return 100.0 * t * c["structures"] / (ns / 1e9)
