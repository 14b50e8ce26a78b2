"""Decode slots filled per dispatched round: the tokens delivered in the
window after each request's first (which its prefill yields), over the
engines' ``steps`` counter."""


def read(run):
    steps = run.telemetry["steps"]
    if not steps:
        return None
    decoded = sum(max(len(run.times(s)) - 1, 0) for s in run.sent)
    return decoded / steps
