"""Roofline-guided spec autotuner + tracked ``BENCH_<rev>.json`` artifacts.

    from repro.tune import tune
    doc = tune(lite_spec(40).replace(n_points=128))   # artifact dict

Submodules: ``search`` (the estimate -> rank -> measure driver),
``frontier`` (deterministic Pareto selection), ``artifact``
(schema-versioned JSON writer/reader/validator).  The CLI entry is
``python benchmarks/run.py --tune-quick --json BENCH_<rev>.json``; two
artifacts diff with ``scripts/bench_diff.py`` (the CI regression gate).
"""
from __future__ import annotations

from repro.tune.artifact import (SCHEMA, ArtifactError, new_artifact,
                                 new_row, read_artifact, resolve_rev,
                                 validate_artifact, write_artifact)
from repro.tune.frontier import dominates, mark_frontier, pareto_frontier
from repro.tune.search import (ANCHOR_NAME, Candidate, anchor_spec,
                               quick_space, tune)

__all__ = [
    "ANCHOR_NAME", "ArtifactError", "Candidate", "SCHEMA", "anchor_spec",
    "dominates", "mark_frontier", "new_artifact", "new_row",
    "pareto_frontier", "quick_space", "read_artifact", "resolve_rev",
    "tune", "validate_artifact", "write_artifact",
]
