"""The system under test, as a configuration file names it.

A configuration's ``suite`` says which operator graphs the program builds:
``paper`` is the paper's GPT-3 layer pair, ``zoo`` a portfolio of the
repository's architecture configs (``archs``) at the suite's batch,
sequence, decode position and tensor parallelism.  This module only calls
the program's public constructors.
"""
from __future__ import annotations

from typing import Dict


def evaluator(cfg: Dict, tier: str):
    """The program's evaluator of this configuration at ``tier``."""
    from repro.perfmodel import evaluator as E
    from repro.perfmodel import workload as W
    s = cfg["suite"]
    if s["kind"] == "paper":
        return E.get_evaluator(tier)
    if s["kind"] == "zoo":
        wls, scen = W.zoo_suite(batch=s["batch"], seq=s["seq"], tp=s["tp"],
                                out_pos=s["out_pos"],
                                archs=tuple(sorted(cfg["archs"])))
        return E.make_evaluator(wls, tier=tier, scenarios=scen)
    raise ValueError(f"unknown suite kind {s['kind']!r}")
