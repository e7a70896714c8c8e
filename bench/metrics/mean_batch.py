"""Mean real batch size of the window's dispatches
(``server.stats["batch_hist"]``)."""


def read(run):
    n = sum(run.batch_hist.values())
    return sum(b * c for b, c in run.batch_hist.items()) / n if n else None
