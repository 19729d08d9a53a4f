"""Share of its roofline that the sweep's Pallas Gram kernels reach, in %:
the least time of every Gram they computed in the window over their
summed device time. Each sweep point runs one batched Gram of ``reps``
trials per strategy; the unquantized baseline contracts in XLA, not in
these kernels, and counts on neither side."""
from bench import roofline, trace

KERNELS = r"^(sign_corr|code_corr|sign_corr_packed)(\.\d+)?$"


def read(ctx):
    c, cfg, peak = ctx["counters"], ctx["config"], ctx["peak"]
    ns = trace.op_ns(ctx["trace"], KERNELS)
    if not c.get("sweeps") or ns <= 0 or peak is None:
        return None
    chips = ctx["chips"]
    reps = c["reps"]
    least = 0.0
    for n in cfg["ns"]:
        for s in cfg["strategies"]:
            if s["method"] == "original":
                continue
            t, _ = roofline.least_seconds(
                reps * roofline.gram_ops(n, cfg["d"]),
                reps * roofline.gram_bytes(n, s.get("wire", "int8"), cfg["d"]),
                chips * roofline.peak_ops(s["method"], peak),
                chips * peak["hbm_bytes_per_s"])
            least += t
    return 100.0 * least * c["sweeps"] / (ns / 1e9)
