"""Mean host time of one serving tick in a closed (offline) mix, ms:
admission, bookkeeping and the dispatches the tick issues."""
import numpy as np


def read(run):
    cell = run.cell
    if not getattr(cell, "closed", False) or not cell.tick_ms:
        return None
    return float(np.mean(cell.tick_ms))
