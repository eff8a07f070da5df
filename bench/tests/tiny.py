"""Cells cut to a size the CPU runs in seconds, for the tests: the same
served paths, comparisons and limits, at tiny widths and loads."""
from __future__ import annotations

from bench import spec


def cell(name: str, bench=None, root=spec.ROOT) -> spec.Cell:
    c = spec.resolve(name, bench, root)
    if c.config["system"] == "stream":
        # Wide enough that logits spread about as at full width (the
        # limits are the cell's own).
        c.config.update(num_layers=2, d_model=512, num_heads=4,
                        num_kv_heads=4, d_ff=1024, vocab_size=2048)
        c.config["plan"].update(point=0, max_batch=2, max_seq_len=64)
        c.config["check"].update(requests=4)
        c.traffic["prompt"] = {"dist": "uniform", "min": 16, "max": 32,
                               "round_up": 16}
        c.traffic["output"] = {"dist": "uniform", "min": 4, "max": 8}
        if "arrivals" in c.traffic:
            c.traffic["arrivals"] = {"process": "poisson", "per_s": 8.0}
    else:
        c.config.update(image_size=32)
        c.config["deployment"]["image_pool"] = 8
        c.config["plan"]["cloud_batch"] = 2
        c.config["check"].update(encodes=6)
        c.traffic["arrivals"] = {"process": "poisson", "per_s": 8.0}
    return c


def run(name: str, seed: int = 2**35 + 1, seconds: float = 1.5,
        trace: bool = False, bench=None, root=spec.ROOT) -> dict:
    from bench import run as bench_run

    return bench_run.run(cell(name, bench, root), seed, seconds, trace,
                         require_chip=False,
                         peaks=bench_run.peaks_for("TPU v5 lite"))
