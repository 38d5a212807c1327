"""Ingest: ``stats_update``'s share of its roofline (memory-bound: the bytes
bound applies) over its calls in the traced stretch, one per tick."""
from bench import roofline


def read(run):
    return roofline.share(run, "stats_update")
