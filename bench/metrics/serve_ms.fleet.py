"""Host ms inside FleetServer.serve per request served."""


def read(run):
    n = run.rec.counters.get("requests", 0)
    return run.span_s("serve") / n * 1e3 if n else None
