"""Routed (token, held-expert) pairs per held expert, per expert layer,
per engine step: the ``routes`` stats of ``jalad.moe.route`` (both sides
of the cut), over the steps, the experts held (``n_routed_experts``) and
the expert layers of the configuration."""
from bench import program_spans


def read(run):
    spans = program_spans.of_run(run)
    cfg = getattr(run.system, "cfg", None)
    if spans is None or cfg is None:
        return None
    routes = spans.named("moe.route")
    steps = sum(1 for s in routes if s.stats.get("side") == "head")
    if not steps:
        return None
    layers = cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]
    total = sum(s.stats["routes"] for s in routes)
    return total / steps / cfg["n_routed_experts"] / layers
