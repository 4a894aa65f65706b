"""Round policies: ADEL-FL and the paper's four baselines (Section IV).

A policy decides, per round t:
  * the deadline T_t (and hence the simulated round wall-clock),
  * each client's batch size S_t^u,
  * the per-(client, layer) contribution mask,
  * the aggregation rule (bias-corrected layer-wise / plain mean / HeteroFL
    width-overlap mean).

All randomness flows through explicit PRNG keys so runs are reproducible.
Each policy's straggler draw runs under a ``repro.draw`` profiler
annotation (:func:`repro.obs.annotate`), inside the runtime's
``repro.plan``.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs

from . import straggler
from .types import AnalysisConfig, Schedule

__all__ = ["RoundPlan", "Policy", "AdelPolicy", "SalfPolicy", "DropPolicy",
           "WaitPolicy", "HeteroFLPolicy", "make_policy"]


@dataclasses.dataclass
class RoundPlan:
    mask: jnp.ndarray          # (U, L) layer contribution mask
    p: jnp.ndarray             # (L,) zero-contributor probabilities (0 where unused)
    batch_sizes: jnp.ndarray   # (U,)
    elapsed: float             # simulated wall-clock consumed by this round
    bias_correct: bool         # Eq. (5) 1/(1-p) correction?
    width_ratios: Optional[np.ndarray] = None   # HeteroFL only


class Policy:
    """Per-round decision maker.

    ``round(key, t, view=None)`` accepts an optional per-round
    :class:`AnalysisConfig` *view* whose ``U``/``P``/``B`` describe the
    cohort actually sampled this round (the fleet engine re-derives one
    per round); with ``view=None`` the policy uses the static config it
    was constructed with, which preserves the original single-population
    behaviour.
    """

    name: str = "base"

    def __init__(self, cfg: AnalysisConfig):
        self.cfg = cfg

    def round(self, key: jax.Array, t: int,
              view: Optional[AnalysisConfig] = None) -> RoundPlan:  # pragma: no cover
        raise NotImplementedError

    def _resolve(self, view: Optional[AnalysisConfig]) -> AnalysisConfig:
        return view if view is not None else self.cfg

    def _fixed_batch(self, view: Optional[AnalysisConfig], T: float):
        """Fixed-batch policies (salf/drop/wait): cached S for the static
        population, re-derived from the cohort view otherwise."""
        if view is None:
            return self.S
        return straggler.fixed_batch(T, self.m, view)

    def describe(self) -> dict:
        return {"name": self.name}


class AdelPolicy(Policy):
    """ADEL-FL: Problem-2-optimized deadlines + B3 batch sizes + Eq. (5)."""

    name = "adel"

    def __init__(self, cfg: AnalysisConfig, schedule: Schedule):
        super().__init__(cfg)
        self.schedule = schedule

    def round(self, key, t, view=None):
        cfg = self._resolve(view)
        T_t = float(self.schedule.T[t])
        with obs.annotate("draw"):
            mask, p, S, _ = straggler.sample_round(key, T_t, self.schedule.m,
                                                   cfg)
        return RoundPlan(mask=mask, p=p, batch_sizes=S, elapsed=T_t,
                         bias_correct=True)

    def describe(self):
        return {"name": self.name, "m": self.schedule.m,
                "T": self.schedule.T.tolist(), "solver": self.schedule.solver}


class SalfPolicy(Policy):
    """SALF [31]: layer-wise aggregation with bias correction, but FIXED
    deadline T_max/R and one FIXED batch size for every user (no joint
    optimization, no B3 per-user batch scaling)."""

    name = "salf"

    def __init__(self, cfg: AnalysisConfig, m: float):
        super().__init__(cfg)
        self.m = float(m)
        self.T_t = cfg.T_max / cfg.R
        self.S = straggler.fixed_batch(self.T_t, self.m, cfg)

    def round(self, key, t, view=None):
        cfg = self._resolve(view)
        S_fix = self._fixed_batch(view, self.T_t)
        with obs.annotate("draw"):
            mask, p, _ = straggler.sample_round_fixed(key, self.T_t, S_fix,
                                                      cfg)
        S = jnp.full((cfg.U,), S_fix)
        return RoundPlan(mask=mask, p=p, batch_sizes=S, elapsed=self.T_t,
                         bias_correct=True)

    def describe(self):
        return {"name": self.name, "m": self.m, "T": self.T_t,
                "S_fixed": float(self.S)}


class DropPolicy(Policy):
    """Drop-Stragglers [17]: fixed deadline; a client counts only if it
    finished the FULL model in time (z_u >= L); late clients are discarded."""

    name = "drop"

    def __init__(self, cfg: AnalysisConfig, m: float):
        super().__init__(cfg)
        self.m = float(m)
        self.T_t = cfg.T_max / cfg.R
        self.S = straggler.fixed_batch(self.T_t, self.m, cfg)

    def round(self, key, t, view=None):
        cfg = self._resolve(view)
        S_fix = self._fixed_batch(view, self.T_t)
        with obs.annotate("draw"):
            P, B = jnp.asarray(cfg.P), jnp.asarray(cfg.B_eff)
            lam = P / S_fix * jnp.maximum(self.T_t - B, 0.0)
            z = straggler.sample_depths(key, lam)
        full = (z >= cfg.L).astype(jnp.float32)                  # (U,)
        mask = jnp.broadcast_to(full[:, None], (cfg.U, cfg.L))
        S = jnp.full((cfg.U,), S_fix)
        return RoundPlan(mask=mask, p=jnp.zeros(cfg.L), batch_sizes=S,
                         elapsed=self.T_t, bias_correct=False)


class WaitPolicy(Policy):
    """Wait-Stragglers (vanilla synchronous FedAvg [1]): no deadline; the
    round lasts until the slowest client finishes (max_u Gamma(L, S_u/P_u) +
    B_u), so far fewer rounds fit inside T_max."""

    name = "wait"

    def __init__(self, cfg: AnalysisConfig, m: float):
        super().__init__(cfg)
        self.m = float(m)
        self.T_ref = cfg.T_max / cfg.R
        self.S = straggler.fixed_batch(self.T_ref, self.m, cfg)

    def round(self, key, t, view=None):
        cfg = self._resolve(view)
        S_fix = self._fixed_batch(view, self.T_ref)
        with obs.annotate("draw"):
            P, B = jnp.asarray(cfg.P), jnp.asarray(cfg.B_eff)
            # full backprop time = sum of L iid Exp(S/P) = Gamma(L,
            # scale=S/P); with a FIXED batch the slowest device dominates
            # the round clock
            g = jax.random.gamma(key, cfg.L, shape=(cfg.U,)) * (S_fix / P)
            elapsed = float(jnp.max(g + B))
        mask = jnp.ones((cfg.U, cfg.L), jnp.float32)
        S = jnp.full((cfg.U,), S_fix)
        return RoundPlan(mask=mask, p=jnp.zeros(cfg.L), batch_sizes=S,
                         elapsed=elapsed, bias_correct=False)


class HeteroFLPolicy(Policy):
    """HeteroFL [30]: clients train width-reduced submodels matched to their
    capability; aggregation averages each parameter entry over the clients
    whose submodel contains it. Compute per layer scales ~ r^2 (both weight
    matrices shrink), so slow clients nearly always finish their small model.

    With a per-round cohort ``view`` (fleet runs) the capability buckets are
    re-derived from the sampled cohort's P, and ``RoundPlan.width_ratios``
    tells the runtime which width masks to build; the width-overlap mean
    runs on every execution backend (``repro.fl.backends``).
    """

    name = "heterofl"
    LEVELS = (1.0, 0.5, 0.25, 0.125)

    def __init__(self, cfg: AnalysisConfig, m: float):
        super().__init__(cfg)
        self.m = float(m)
        self.T_t = cfg.T_max / cfg.R
        self.ratios = self._capability_ratios(cfg.P)

    @classmethod
    def _capability_ratios(cls, P: np.ndarray) -> np.ndarray:
        """Capability-bucketed width ratios: fastest quartile -> 1.0, etc."""
        P = np.asarray(P)
        order = np.argsort(np.argsort(-P))          # rank 0 = fastest
        quart = (order * len(cls.LEVELS)) // len(P)
        return np.asarray([cls.LEVELS[q] for q in quart], np.float32)

    def round(self, key, t, view=None):
        cfg = self._resolve(view)
        ratios = (self.ratios if view is None
                  else self._capability_ratios(cfg.P))
        with obs.annotate("draw"):
            P, B = jnp.asarray(cfg.P), jnp.asarray(cfg.B_eff)
            S_fix = straggler.fixed_batch(self.T_t, self.m, cfg)
            r = jnp.asarray(ratios)
            # per-layer time Exp(S r^2 / P) -> completed layers
            # ~ Poisson(P (T-B) / (S r^2))
            lam = P / (S_fix * r ** 2) * jnp.maximum(self.T_t - B, 0.0)
            z = straggler.sample_depths(key, lam)
        full = (z >= cfg.L).astype(jnp.float32)
        mask = jnp.broadcast_to(full[:, None], (cfg.U, cfg.L))
        S = jnp.full((cfg.U,), S_fix)
        return RoundPlan(mask=mask, p=jnp.zeros(cfg.L), batch_sizes=S,
                         elapsed=self.T_t, bias_correct=False,
                         width_ratios=ratios)

    def describe(self):
        return {"name": self.name, "m": self.m, "ratios": self.ratios.tolist()}


def make_policy(method: str, cfg: AnalysisConfig, *, schedule: Schedule | None = None,
                m: float | None = None) -> Policy:
    from .scheduler import constant_schedule, solve
    if method == "adel":
        if schedule is None:
            schedule = solve(cfg, "trust-constr")
        return AdelPolicy(cfg, schedule)
    if m is None:
        m = constant_schedule(cfg).m
    if method == "salf":
        return SalfPolicy(cfg, m)
    if method == "drop":
        return DropPolicy(cfg, m)
    if method == "wait":
        return WaitPolicy(cfg, m)
    if method == "heterofl":
        return HeteroFLPolicy(cfg, m)
    raise ValueError(f"unknown method {method!r}")
