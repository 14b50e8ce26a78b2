"""Share of the prompt tokens computed in the window that the paged KV
cache served from resident prefix blocks: the engines' ``shared_hits``
counter (blocks) times the block size, over the prompt tokens of the
requests whose first token came in the window."""


def read(run):
    tokens = sum(s.prompt_len for s in run.sent if run.times(s))
    if not tokens:
        return None
    return 100.0 * run.telemetry["shared_hits"] * run.block_size / tokens
