"""serve_tokens_per_s: tokens generated over the wall time of the whole
timed ``ServeEngine.run``, on the host clock."""


def read(run):
    if run.kind != "serve":
        return None
    return run.tokens / run.window_s
