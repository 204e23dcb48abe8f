"""Share of the traced window in which no operation ran on the device,
%, averaged over the chips used."""
from lib import trace as trace_lib


def read(run):
    if run.trace is None or not run.trace["devices"] or run.config["kind"] != "serving" or run.cell.closed:
        return None
    return 100.0 * (1.0 - trace_lib.busy_seconds(run.trace)
                    / trace_lib.window_seconds(run.trace))
