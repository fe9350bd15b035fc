"""Tiny copies of the cells for CPU tests: the harness's whole run past its
look for a chip, at sizes a test run can hold."""

from __future__ import annotations

import json
import os
import time

HERE = os.path.dirname(os.path.abspath(__file__))

CPU = {"platform": "cpu", "kind": "cpu", "count": 1}

# tiny cell -> (configuration, traffic) files, the real cell it stands for
CELLS = {
    "poisson128-halo4": ("data/tiny-dmda.json", "../traffic/halo4-closed.json"),
    "poisson128-cg50": ("data/tiny-dmda.json", "../traffic/cg-sets50.json"),
    "phi35moe-chat32": ("data/tiny-moe.json", "data/tiny-chat.json"),
}


def load(rel: str) -> dict:
    with open(os.path.join(HERE, rel)) as f:
        return json.load(f)


def cell(name: str):
    """-> (cell, config, traffic) of the tiny copy of cell ``name``."""
    cfg, tr = CELLS[name]
    return {"name": name, "chips": 1}, load(cfg), load(tr)


def run(name: str, seed: int = 7, seconds: float = 0.5, trace: bool = False):
    """The harness's run of the tiny copy of ``name`` on the CPU."""
    from bench import harness
    c, cfg, tr = cell(name)
    return harness.run_cell(name, seed, seconds, trace,
                            t_start=time.perf_counter(), cell=c,
                            config=cfg, traffic=tr, device=CPU,
                            log=lambda *a: None)
