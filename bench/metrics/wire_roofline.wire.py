"""Share of its roofline that the wire reaches, in %: the least time of
the paper's all-gather of each structure's payload (``wire_bytes``, from
``WirePlan.comm_report``; each chip receives the other machines' share)
over the measured collective time per structure, averaged over chips."""
from bench import roofline_wire, trace


def read(ctx):
    c, cfg, peak = ctx["counters"], ctx["config"], ctx["peak"]
    ns = trace.op_ns(ctx["trace"], trace.COLLECTIVE.pattern)
    if not c.get("structures") or not c.get("wire_bytes") or ns <= 0 \
            or peak is None:
        return None
    t = roofline_wire.wire_least_seconds(c["wire_bytes"], cfg["machines"],
                                         peak)
    return 100.0 * t * c["structures"] / (ns / 1e9)
