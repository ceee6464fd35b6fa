"""monitor_hook_ms.serve: host milliseconds per call of the monitor hook
(``publish_step_utilization`` as the serve path imports it), from the
harness's span around each call in the traced window."""


def read(run):
    calls = run.spans.get("monitor_hook")
    if run.kind != "serve" or not calls:
        return None
    return 1e3 * sum(calls) / len(calls)
