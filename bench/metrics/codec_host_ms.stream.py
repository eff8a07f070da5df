"""Host ms inside the codec (encode and decode calls) per engine step."""


def read(run):
    n = run.span_n("step")
    if not n:
        return None
    return (run.span_s("encode") + run.span_s("decode")) / n * 1e3
