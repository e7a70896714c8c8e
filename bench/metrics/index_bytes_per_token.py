"""Index bytes on the device (WTBC, plus DRB where the deployment keeps it:
``engine.space_report()["total"]``) per word of the collection."""


def read(run):
    return run.index_bytes / run.n_tokens
