"""Fitted cost model: measured samples in, per-step cost predictor out.

The analytic bridge (`measure.analytic_step_us`) is ordinally honest
but its absolute scale is guessed from arch constants. The fitted model
closes that gap with data: a least-squares fit of per-step cost against
the physically meaningful regressors

    x0 = 1                               (fixed per-step dispatch)
    x1 = streamed blocks                 (expected blocks x active frac
                                          under compaction, all blocks
                                          dense)
    x2 = streamed element volume         (x1 * T^2 * d -- the MAC/HBM
                                          term)

per backend (jnp / pallas / interpret have distinct throughput, so each
gets its own coefficients; a backend with no samples falls back to the
analytic estimate). Non-negative clamping keeps a noisy fit from ever
predicting negative cost.

Training data comes from two places:

  * the tune-time measured `Sample`s of the current sweep, and
  * recorded BENCH history (`load_bench_samples`): the append-safe
    ``BENCH_*.json`` files `benchmarks.common.write_json` accumulates
    carry kernel-step rows ("feature_step_*", "frontier_step_*") whose
    derived strings name the block count and feature width -- free
    extra observations of exactly the regressors above, from every
    bench run this machine ever recorded. Parsing is best-effort: a
    row that does not parse contributes nothing (history must never
    break a tune).
"""
from __future__ import annotations

import dataclasses
import json
import os
import re

import numpy as np

from repro.api.plan import ExecutionPlan
from repro.autotune.measure import (Sample, active_tile_fraction,
                                    analytic_step_us, expected_blocks)
from repro.autotune.profile import GraphProfile


def features_of(profile: GraphProfile, plan: ExecutionPlan) -> np.ndarray:
    """The regressor vector [1, streamed_blocks, streamed_volume]."""
    t, d = plan.tile, max(profile.feature_dim, 1)
    nb = expected_blocks(profile.n, profile.m, t)
    af = active_tile_fraction(profile.mean_density, t)
    fetched = nb * (af if plan.compact else 1.0)
    return np.asarray([1.0, fetched, fetched * t * t * d],
                      dtype=np.float64)


@dataclasses.dataclass
class CostModel:
    """Per-backend least-squares fit of step_us over `features_of`."""

    coef: dict                 # backend -> (3,) float64 coefficients
    n_samples: int = 0

    @classmethod
    def fit(cls, samples: list, profile: GraphProfile) -> "CostModel":
        """Fit from measured samples (analytic-sourced ones are
        excluded: fitting the model to its own fallback would launder
        guesses into 'data'). Needs >= 3 points per backend for the
        3-coefficient fit; fewer points leave that backend analytic."""
        by_backend: dict[str, list] = {}
        for s in samples:
            if getattr(s, "source", "measured") != "measured":
                continue
            by_backend.setdefault(s.plan.relax_mode, []).append(s)
        coef = {}
        for backend, ss in by_backend.items():
            if len(ss) < 3:
                continue
            x = np.stack([np.asarray(s.features, dtype=np.float64)
                          if s.features is not None
                          else features_of(profile, s.plan)
                          for s in ss])
            y = np.asarray([s.step_us for s in ss], dtype=np.float64)
            sol, *_ = np.linalg.lstsq(x, y, rcond=None)
            coef[backend] = sol
        return cls(coef=coef,
                   n_samples=sum(len(v) for v in by_backend.values()))

    def predict(self, profile: GraphProfile,
                plan: ExecutionPlan) -> float:
        """Predicted step_us; analytic fallback for backends the fit
        never saw, and clamped to a strictly positive floor."""
        c = self.coef.get(plan.relax_mode)
        if c is None:
            return analytic_step_us(profile, plan)
        return float(max(features_of(profile, plan) @ c, 1e-3))


# ------------------------------------------------------------------ #
# BENCH_*.json history -> extra training samples
# ------------------------------------------------------------------ #
# rows like:  feature_step_min_plus_2k_d8 , 512.3 ,
#             "power-law |V|=2048 blocks=519 d=8 eff_gflops=..."
_ROW_RE = re.compile(r"(?:feature|frontier)_step_")
_KV_RE = re.compile(r"\b(blocks|d|\|V\|)=(\d+)")


def load_bench_samples(paths=None, tile_default: int = 64) -> list:
    """Best-effort parse of recorded bench history into Samples.

    `paths` defaults to the repo-root BENCH files next to the
    `benchmarks` package (where `write_json` appends when BENCH_OUT is
    unset). Every failure mode -- missing file, corrupt JSON, legacy
    layout, unparseable derived string -- contributes zero samples,
    never an exception."""
    if paths is None:
        root = os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.dirname(os.path.abspath(__file__)))))
        paths = [os.path.join(root, f"BENCH_{tag}.json")
                 for tag in ("kernels", "features")]
    out: list = []
    for path in paths:
        try:
            with open(path) as f:
                data = json.load(f)
        except (OSError, json.JSONDecodeError, ValueError):
            continue
        runs = data.get("runs", []) if isinstance(data, dict) else []
        for run in runs:
            for row in run.get("rows", []) or []:
                s = _row_to_sample(row, tile_default)
                if s is not None:
                    out.append(s)
    return out


def _row_to_sample(row: dict, tile_default: int):
    """One bench row -> Sample, or None when it isn't a step-cost row
    with a parseable shape."""
    try:
        name, us = row.get("name", ""), float(row.get("us_per_call", 0))
    except (TypeError, ValueError):
        return None
    if not _ROW_RE.match(name) or us <= 0:
        return None
    kv = dict(_KV_RE.findall(row.get("derived", "") or ""))
    if "blocks" not in kv:
        return None
    d = int(kv.get("d", 1))
    blocks = float(kv["blocks"])
    # bench step rows are dense jnp relax steps at the bench tile, so
    # their regressors are exact: every block streamed, T^2*d volume
    plan = ExecutionPlan(relax_mode="jnp", compact=False,
                         tile=tile_default,
                         feature_dim=d if d > 1 else 0)
    feats = (1.0, blocks, blocks * tile_default * tile_default * d)
    return Sample(plan=plan, step_us=us, steps=1, wall_s=us * 1e-6,
                  source="measured", features=feats)
