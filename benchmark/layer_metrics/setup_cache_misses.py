"""Compile path: how many of set-up's backend compiles the persistent
cache could not serve and had to store: 0 in a warm process; what
separates a machine's first set-up from the later ones. The program's
``compile`` records before the window's opening that the cache's events
marked as misses (``benchmark/setup_phases.py``)."""

from benchmark import setup_phases


def read(ctx):
    return setup_phases.reading(ctx, "cache_misses")
