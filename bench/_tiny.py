"""Sizes at which the tests under ``bench/`` run each cell on the CPU (the
kernel in interpret mode): the cells' own configurations and mixes, cut
down, and a run of a few seconds.

A cell's sizes are its own file, ``bench/tiny/<cell>.json``:
``{"config": {...}, "traffic": {...}}``, overrides of the keys of the
cell's configuration and mix.  A cell brings its file; none here is
edited."""
import json
import os

from bench.harness import ROOT

SECONDS = 1.5


def tiny_sizes(cell: str, root: str = ROOT) -> dict:
    """The overrides of ``cell``, from its file under ``root``."""
    with open(os.path.join(root, "bench", "tiny", f"{cell}.json")) as f:
        return json.load(f)
