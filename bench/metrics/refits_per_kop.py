"""Approved refits per thousand ops in the window's rounds: how much of
the paper's loop (drift, refit, reconfigure) the traffic drives."""


def read(run):
    cell = run.cell
    if run.config["kind"] != "fleet":
        return None
    ops = sum(cell.round_ops)
    return 1e3 * cell.n_refits / ops if ops else None
