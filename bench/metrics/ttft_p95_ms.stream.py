"""95th percentile, over every request due in the whole window, of its
first token minus its scheduled arrival: ``bench/end_to_end/
ttft_p95_ms.py``'s arithmetic, read in a traced run. The chat cell's 45
first tokens a window spread too widely from run to run to hold it to an
end-to-end bound."""
from bench import harness


def read(run):
    ttft = harness.first_token_ms(run.rec, run.win)
    return harness.percentile(ttft, 95) if ttft else None
