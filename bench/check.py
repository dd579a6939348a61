"""The comparison that decides ``correct``.

Once the window has closed, a sample of the window's decisions, drawn
from the seed, is recomputed by the float64 reference (``reference.py``),
which replays each sampled tenant from its observations alone: its own
support fits, its own fit chain, RGPE weights, mixture posteriors and
acquisition. For each sampled decision the regret is the gap by which
the reference's acquisition of the configuration the program launched
lies below the reference's best, relative to that best (a decision whose
best acquisition is all but zero picks freely and counts as ``flat``).

Numbers compared, each against its limit in
``bench/limits/<workload>.json``:

- ``unanswered``: outcomes due in the window that no decision answered
  within a minute of the close (limit 0);
- ``decisions_checked``: sampled decisions recomputed (at least the
  limit);
- ``regret_share``: the share of the checked decisions whose regret
  exceeds ``REGRET_CUT`` (the decisions: posterior, RGPE mixture,
  acquisition);
- ``fit_gap_share``: the share of the sampled tenants' models whose
  float64 negative log marginal likelihood at the hyperparameters the
  program holds after its last fit exceeds that at the reference's fit
  of the same observations by more than ``FIT_CUT`` nats (the fit leg).

The GP fit chain is chaotic: Adam's normalised steps turn roundoff into
O(learning rate) moves along flat directions of the likelihood, so two
sound implementations reach different hyperparameters for some models,
and a few decisions differ. Hence shares, and the fit's likelihood
rather than its hyperparameters. The widest regret and the widest NLML
excess (``fit_nlml_gap``) swing from seed to seed; they are printed, not
compared.

``Replay.readings(variant)`` puts a ``reference.Variant`` in the
program's place instead: it decides every sampled decision and fits
every model the program held. The control is the reference in float32
with its products at ``high`` (``CONTROL``); ``FAULTS`` are the planted
faults of the fit and RGPE legs.
"""
from __future__ import annotations

import json
import os
from typing import Dict, List, Optional

import numpy as np

from bench import reference as ref
from bench.cohort import Cohort, subseed
from bench.scout import space_configs

HERE = os.path.dirname(os.path.abspath(__file__))
REGRET_CUT = 0.01       # a decision differs above this regret
FIT_CUT = 0.5           # a fit is worse above this NLML excess (nats)
FLAT_EI = 1e-6          # standardised EI below which any pick ties
FLAT_EHVI = 1e-6        # EHVI below this share of the front's volume

CONTROL = {"high": ref.Variant(passes=3)}
LOWER = {"bf16": ref.Variant(passes=1)}
FAULTS = {"fit_init": ref.Variant(fit="init"),
          "adam_nobias": ref.Variant(fit="adam_nobias"),
          "rgpe_uniform": ref.Variant(rgpe="uniform")}


def limits_for(workload: str) -> Dict[str, float]:
    with open(os.path.join(HERE, "limits", f"{workload}.json")) as f:
        return json.load(f)["limits"]


class Replay:
    """The reference's replay of a run's sampled decisions and of the
    fits the program held, shared by the program's readings and those of
    every variant put in its place."""

    def __init__(self, record, config: Dict, seed: int, sample: int):
        self.record, self.config = record, config
        self.cohort = Cohort(config, seed)
        rng = np.random.default_rng(subseed(seed, 5))
        launched = [d for d in record.decisions if d.kind == "launch"
                    and d.rid in record.sessions]
        pick = sorted(rng.choice(len(launched), min(sample, len(launched)),
                                 replace=False)) if launched else []
        self.chosen = [launched[i] for i in pick]
        self.fitted = {rid: s["fit"] for rid, s in record.sessions.items()
                       if s["fit"]}
        self.rids = sorted({d.rid for d in self.chosen})
        # one fit check per sampled tenant the program still holds
        self.upto: Dict[int, int] = {}
        for d in self.chosen:
            t = record.tenants[d.rid].index
            self.upto[t] = max(self.upto.get(t, 0), d.n_obs)
        for rid in self.rids:
            for n, _, _ in self.fitted.get(rid, {}).values():
                t = record.tenants[rid].index
                self.upto[t] = max(self.upto[t], n)
        self.runs = self.cohort.repository_runs()
        self.grid = space_configs()
        self.measures = list(config["objectives"]) + [
            c["measure"] for c in config.get("constraints", ())]
        self.exact = self._replay(ref.EXACT)

    def _replay(self, variant: ref.Variant) -> Dict:
        support = ref.Support(self.runs, self.config, variant)
        if self.config["method"] == "karasu":
            support.fit([(w, m) for w in support.data
                         for m in self.measures])
        reps = {rid: ref.Tenant64(self.record.tenants[rid],
                                  self.record.sessions[rid]["obs"],
                                  self.config, self.grid, support, variant)
                for rid in self.rids}
        if reps:
            ref.fit_chains(list(reps.values()), self.upto)
        return {"reps": reps, "acq": {}}

    def acq(self, replay: Dict, rid: int, n: int):
        """(remaining configurations, acquisition) of a decision; None
        where the replay's arithmetic breaks down (a failed Cholesky)."""
        cache = replay["acq"]
        if (rid, n) not in cache:
            try:
                rem, a = replay["reps"][rid].acquisition(n)
                cache[(rid, n)] = (rem, np.nan_to_num(a, nan=-np.inf))
            except np.linalg.LinAlgError:
                cache[(rid, n)] = None
        return cache[(rid, n)]

    def raw(self, variant: Optional[ref.Variant] = None) -> Dict:
        """Per sampled decision its regret (None where the decision is
        flat), and per model held its NLML excess: the program's, or with
        ``variant`` those of that variant put in the program's place."""
        other = None if variant is None else self._replay(variant)
        config = self.config
        moo = len(config["objectives"]) > 1
        regrets: List[Optional[float]] = []
        for d in self.chosen:
            rem, a = self.acq(self.exact, d.rid, d.n_obs)
            best = float(a.max())
            floor = FLAT_EI
            if moo:
                obs = self.exact["reps"][d.rid].obs[:d.n_obs]
                pts = np.array([[ms[o] for o in config["objectives"]]
                                for _, ms, _ in obs])
                floor = FLAT_EHVI * max(ref.hv_nd(ref.pareto_front(pts),
                                                  pts.max(0) * 1.1 + 1e-9),
                                        1e-300)
            if other is None:
                ci = d.ci
            else:
                got = self.acq(other, d.rid, d.n_obs)
                ci = None if got is None else got[0][int(np.argmax(got[1]))]
            if ci not in rem:            # no answer, or one profiled before
                regrets.append(float("inf"))
            elif best <= floor:
                regrets.append(None)
            else:
                regrets.append((best - float(a[rem.index(ci)])) / best)

        gaps: List[float] = []
        noise = config["bo"]["noise"]
        for rid in self.rids:
            rep = self.exact["reps"][rid]
            for m, (n, ls, sf) in sorted(self.fitted.get(rid, {}).items()):
                if other is not None:
                    ls, sf = other["reps"][rid].fits[(m, n)]
                y = rep.y(m, n)
                want = ref.nlml(rep.x[:n], y, *rep.fits[(m, n)], noise)
                try:
                    got = ref.nlml(rep.x[:n], y, ls, sf, noise)
                except np.linalg.LinAlgError:
                    got = float("inf")
                gaps.append(got - want)
        return {"regrets": regrets, "gaps": gaps}

    def readings(self, variant: Optional[ref.Variant] = None
                 ) -> Dict[str, float]:
        """The numbers compared (and two printed beside them)."""
        return summarise(self.raw(variant), self.record.unanswered)


def summarise(raw: Dict, unanswered: int) -> Dict[str, float]:
    regrets = [r for r in raw["regrets"] if r is not None]
    gaps = raw["gaps"]
    checked = len(raw["regrets"])
    return {"unanswered": float(unanswered),
            "decisions_checked": float(checked),
            "regret_share": (sum(1 for r in regrets if r > REGRET_CUT)
                             / checked if checked else 1.0),
            "fit_gap_share": (sum(1 for g in gaps if g > FIT_CUT)
                              / len(gaps) if gaps else 0.0),
            "regret_max": max(regrets, default=0.0),
            "fit_nlml_gap": max(gaps, default=0.0)}


def readings(record, config: Dict, seed: int, sample: int
             ) -> Dict[str, float]:
    """The program's numbers, from a run's record."""
    return Replay(record, config, seed, sample).readings()


def judge(r: Dict[str, float], limits: Dict[str, float]) -> List:
    """[(name, reading, limit, ok)] for every number compared."""
    return [("unanswered", r["unanswered"], 0.0, r["unanswered"] == 0),
            ("decisions_checked", r["decisions_checked"],
             limits["min_decisions_checked"],
             r["decisions_checked"] >= limits["min_decisions_checked"]),
            ("regret_share", r["regret_share"], limits["regret_share"],
             r["regret_share"] <= limits["regret_share"]),
            ("fit_gap_share", r["fit_gap_share"], limits["fit_gap_share"],
             r["fit_gap_share"] <= limits["fit_gap_share"])]
