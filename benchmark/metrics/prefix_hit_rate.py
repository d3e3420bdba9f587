"""Prompt tokens served from shared blocks (GenRequest.shared_len) over all
prompt tokens of the requests sent."""

UNIT = "%"
SOURCE = "program_counter"
LAYER = "pager"
MOVES = "ttft_p50_ms"


def read(run):
    sent = [r for r in run.requests if "shared_len" in r]
    if not sent:
        return None
    return 100.0 * sum(r["shared_len"] for r in sent) / sum(r["prompt_len"]
                                                             for r in sent)
