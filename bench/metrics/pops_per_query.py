"""Mean ``RowResult.pops`` of the answered requests: segments the DR search
loop popped."""
import numpy as np


def read(run):
    pops = [r.row.pops for r in run.completed if r.row.pops is not None]
    return float(np.mean(pops)) if pops else None
