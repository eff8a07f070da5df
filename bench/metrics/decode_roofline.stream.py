"""Decode programs' share of their memory roofline: the bytes the head
and tail decode programs must move each step (the reference's
``decode_bytes``: each side's weights once, only the experts hit of the
routed ones, the live slots' cache rows up to their positions; the
experts hit and the rows from the stats of ``jalad.moe.route``), at HBM
bandwidth, over the device time of the programs launched in
``jalad.stream.head`` and ``jalad.stream.tail``, in percent."""
from bench import program_spans


def read(run):
    spans = program_spans.of_run(run)
    ref = getattr(run.system, "ref", None)
    if spans is None or not hasattr(ref, "decode_bytes"):
        return None
    routes = spans.named("moe.route")
    if not routes:
        return None
    cfg = run.system.cfg
    nbytes = sum(ref.decode_bytes(cfg, s.stats["side"],
                                  s.stats["experts_hit"], s.stats["rows"])
                 for s in routes)
    device_s = sum(s.device_s for s in spans.spans
                   if s.name in ("stream.head", "stream.tail")
                   and "stream.step" in s.up)
    if device_s <= 0 or not spans.chips:
        return None
    return 100.0 * nbytes / run.peaks["hbm_bytes_per_s"] / device_s
