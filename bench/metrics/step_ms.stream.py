"""Host ms per engine step of the token stream, its joins left out."""


def read(run):
    n = run.span_n("step")
    if not n:
        return None
    return (run.span_s("step") - run.span_s("join")) / n * 1e3
