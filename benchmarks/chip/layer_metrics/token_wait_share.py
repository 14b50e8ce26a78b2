"""Share of the instances' time spent ready but held back by the node
scheduler (``TokenScheduler``): the engines' ``token_wait_s`` counter
over the window, summed over instances, over instances x window
seconds.  None where the program has no such counter."""


def read(run):
    wait = run.telemetry.get("token_wait_s")
    if wait is None:
        return None
    instances = run.cfg["deployment"]["instances"]
    return 100.0 * wait / (instances * run.seconds)
