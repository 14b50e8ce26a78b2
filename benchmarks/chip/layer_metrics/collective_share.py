"""Share of the device's busy time in which a collective operation ran
(all-reduce, all-gather, reduce-scatter, collective-permute, all-to-all,
their asynchronous halves included): the trace's ``collective_s`` over
its ``busy_s``, each a mean over the chips.  None where the trace shows
no collective, as on one chip."""


def read(run):
    if run.trace is None or run.trace["busy_s"] <= 0:
        return None
    if not run.trace["collective_s"]:
        return None
    return 100.0 * run.trace["collective_s"] / run.trace["busy_s"]
