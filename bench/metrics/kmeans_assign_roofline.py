"""Share of its roofline the ``kmeans_assign`` kernel reaches in the traced
ops: one Lloyd step over all n rows per op."""
from bench import readers, work


def read(run):
    cfg = run.cell.config
    flops, nbytes = work.kmeans_assign(run.rows, int(cfg["cols"]),
                                       int(cfg["k"]))
    return readers.roofline(run, "kmeans_assign", flops, nbytes)
