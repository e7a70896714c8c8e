"""Dead beam lanes over all lanes the DR loop descended:
``padded / (pops + padded)`` over the answered requests, in %."""


def read(run):
    rows = [r.row for r in run.completed if r.row.padded is not None]
    pops = sum(r.pops for r in rows)
    pad = sum(r.padded for r in rows)
    return 100.0 * pad / (pops + pad) if pops + pad else None
