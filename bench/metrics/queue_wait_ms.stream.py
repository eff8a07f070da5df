"""Mean ms a request waits for its join: ``jalad.stream.join`` start minus
``jalad.stream.submit`` start of the same ``uid``, over the joins whose
submit the trace holds."""
from bench import program_spans


def read(run):
    spans = program_spans.of_run(run)
    return None if spans is None else spans.queue_wait_ms()
