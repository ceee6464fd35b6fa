"""slot_occupancy.serve: decoded tokens over decode steps x slots, from the
timed ``ServeEngine.run``'s own counts (the first token of each request
comes from its prefill and is not counted)."""


def read(run):
    if run.kind != "serve" or not run.steps:
        return None
    return 100.0 * run.decoded / (run.steps * run.slots)
