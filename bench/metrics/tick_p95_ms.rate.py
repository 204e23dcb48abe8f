"""95th percentile of host time per serving tick in an open mix, ms,
the tick's token readback included: the ticks that carry a prefill set
the inter-token tail."""
import numpy as np


def read(run):
    cell = run.cell
    if getattr(cell, "closed", True) or not cell.tick_ms:
        return None
    return float(np.percentile(cell.tick_ms, 95))
