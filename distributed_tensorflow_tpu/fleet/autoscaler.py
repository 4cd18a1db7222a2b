"""SLO-driven fleet autoscaling: one policy for sim and real engines.

The :class:`Autoscaler` closes the loop the fleet tier left open: the
router can add, drain, and migrate replicas, but nothing DECIDED when.
This object does, from exactly two kinds of input the caller feeds it —
per-request SLO verdicts (``record``: did this request meet its TTFT /
inter-token target?) and the router's own ``stats()`` snapshot read at
``evaluate`` time.  Because both inputs exist identically for
:class:`fleet.sim.SimEngine` fleets (virtual time) and real
``serve.Engine`` fleets (wall time), the SAME policy object drives
both — the simulator is how a policy change is rehearsed at million-
request scale before it touches devices (docs/FLEET_SIM.md).

Policy (deliberately simple, deterministic, and auditable):

* **scale-out** when the sliding-window SLO attainment drops below
  ``target_attainment`` OR the fleet-wide queue backlog exceeds
  ``backlog_high`` × total slots — each trips ``router.add_replica``
  with a fresh engine from ``engine_factory``.
* **scale-in** when the window met the target, nothing is queued, and
  the total in-flight load would fit in ``util_low`` of the remaining
  capacity — the least-loaded replica (ties: highest id, i.e. newest)
  is drained with ``migrate=True`` (in-flight requests move with their
  progress; zero-downtime semantics from PR 8) and removed.
* stabilization is ASYMMETRIC (the HPA convention): scale-out may fire
  on every evaluation — a burst ramps faster than any cooldown — while
  scale-in waits ``cooldown_s`` after the last action of either kind;
  ``min_replicas`` / ``max_replicas`` rail both directions.

The objective the bench scores is SLO attainment per replica-second
(``charge`` integrates provisioned replica-time) — a policy only wins
by buying attainment with capacity at the right moments, not by
pinning the fleet at ``max_replicas``.

Metrics (``dttpu_autoscaler_*``, docs/OBSERVABILITY.md): ``replicas``
gauge, ``attainment`` window gauge, ``scale_out_total`` /
``scale_in_total`` counters, ``replica_seconds_total`` counter.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, List, Optional, Tuple

from ..obs import metrics as metrics_lib
from .router import Router

__all__ = ["SLO", "Autoscaler"]


@dataclasses.dataclass(frozen=True)
class SLO:
    """p99 service-level targets: submit-to-first-token and mean
    inter-token gap (TPOT) per request."""
    ttft_s: float = 2.0
    itl_s: float = 0.1

    def __post_init__(self):
        if not self.ttft_s > 0 or not self.itl_s > 0:
            raise ValueError("SLO targets must be positive")


class Autoscaler:
    """See the module docstring.  The caller owns the cadence: feed
    ``record`` as requests finish, ``charge`` as time passes, and call
    ``evaluate(now)`` every ``eval_interval_s`` — wall seconds for a
    real fleet, virtual seconds under :class:`fleet.sim.FleetSim`."""

    def __init__(self, router: Router,
                 engine_factory: Callable[[], Any],
                 slo: SLO, *,
                 min_replicas: int = 1, max_replicas: int = 8,
                 target_attainment: float = 0.99,
                 eval_interval_s: float = 15.0,
                 cooldown_s: float = 60.0,
                 backlog_high: float = 2.0,
                 util_low: float = 0.40,
                 drain_timeout_s: Optional[float] = 30.0,
                 registry: Optional[metrics_lib.Registry] = None):
        if min_replicas < 1 or max_replicas < min_replicas:
            raise ValueError(
                f"need 1 <= min_replicas <= max_replicas; got "
                f"{min_replicas}..{max_replicas}")
        self.router = router
        self.engine_factory = engine_factory
        self.slo = slo
        self.min_replicas = int(min_replicas)
        self.max_replicas = int(max_replicas)
        self.target_attainment = float(target_attainment)
        self.eval_interval_s = float(eval_interval_s)
        self.cooldown_s = float(cooldown_s)
        self.backlog_high = float(backlog_high)
        self.util_low = float(util_low)
        self.drain_timeout_s = drain_timeout_s
        self.scale_outs = 0
        self.scale_ins = 0
        self.replica_seconds = 0.0
        self.history: List[tuple] = []
        self._last_action_at: Optional[float] = None
        self._w_ttft_ok = 0
        self._w_ttft_n = 0
        self._w_itl_ok = 0
        self._w_itl_n = 0
        reg = registry if registry is not None else metrics_lib.REGISTRY
        self._m_replicas = reg.gauge(
            "dttpu_autoscaler_replicas",
            "Replicas behind the router at the last evaluation.")
        self._m_attainment = reg.gauge(
            "dttpu_autoscaler_attainment",
            "Sliding-window SLO attainment (min of TTFT and "
            "inter-token) at the last evaluation.")
        self._m_out = reg.counter(
            "dttpu_autoscaler_scale_out_total",
            "Replicas added by the autoscaler.")
        self._m_in = reg.counter(
            "dttpu_autoscaler_scale_in_total",
            "Replicas drained (migrate=True) and removed by the "
            "autoscaler.")
        self._m_seconds = reg.counter(
            "dttpu_autoscaler_replica_seconds_total",
            "Provisioned replica-time integrated by the driver "
            "(virtual seconds under the simulator).")

    # ---------------------------------------------------------- inputs

    def record(self, ttft_ok: Optional[bool] = None,
               itl_ok: Optional[bool] = None) -> None:
        """One request's SLO verdicts into the current window (either
        half may arrive alone — TTFT lands at first token, the
        inter-token verdict at retirement)."""
        if ttft_ok is not None:
            self._w_ttft_n += 1
            if ttft_ok:
                self._w_ttft_ok += 1
        if itl_ok is not None:
            self._w_itl_n += 1
            if itl_ok:
                self._w_itl_ok += 1

    def charge(self, dt_s: float, replicas: int) -> None:
        """Integrate provisioned replica-time (the cost denominator)."""
        amount = dt_s * replicas
        self.replica_seconds += amount
        self._m_seconds.inc(amount)

    def window_attainment(self) -> float:
        """min(TTFT, inter-token) attainment over the current window;
        an empty window counts as attained (no evidence of trouble)."""
        a = (self._w_ttft_ok / self._w_ttft_n if self._w_ttft_n
             else 1.0)
        b = self._w_itl_ok / self._w_itl_n if self._w_itl_n else 1.0
        return min(a, b)

    # --------------------------------------------------------- decide

    def evaluate(self, now: float) -> Optional[Tuple[str, int]]:
        """One policy evaluation at time ``now`` (the caller's clock —
        wall or virtual).  Returns ``("scale_out", rid)`` /
        ``("scale_in", rid)`` when an action was taken, else None.
        The window counters reset every evaluation."""
        stats = self.router.stats()
        replicas = len(stats)
        slots = sum(s.num_slots for s in stats.values())
        queued = sum(s.queued for s in stats.values())
        inflight = sum(s.inflight for s in stats.values())
        att = self.window_attainment()
        self._w_ttft_ok = self._w_ttft_n = 0
        self._w_itl_ok = self._w_itl_n = 0
        self._m_attainment.set(att)
        action: Optional[Tuple[str, int]] = None
        cooled = (self._last_action_at is None
                  or now - self._last_action_at >= self.cooldown_s)
        if replicas < self.min_replicas:
            # heal: the fleet fell below its floor (correlated kill,
            # quarantine) — restore capacity regardless of cooldown or
            # window attainment, one replica per evaluation.
            rid = self.router.add_replica(self.engine_factory())
            self.scale_outs += 1
            self._m_out.inc()
            action = ("scale_out", rid)
            self._last_action_at = now
            self.history.append((round(now, 9), action[0], action[1]))
        elif replicas > 0:
            # scale-out is NOT gated on cooldown: a burst ramps faster
            # than any flap-guard, and an extra replica is the cheap
            # mistake.  Scale-in is the risky direction — it waits.
            if replicas < self.max_replicas and (
                    att < self.target_attainment
                    or queued > self.backlog_high * slots):
                rid = self.router.add_replica(self.engine_factory())
                self.scale_outs += 1
                self._m_out.inc()
                action = ("scale_out", rid)
            elif (cooled
                  and replicas > self.min_replicas
                  and att >= self.target_attainment
                  and queued == 0
                  and inflight < self.util_low * slots
                  * (replicas - 1) / replicas):
                victim = self._scale_in_victim(stats)
                if victim is not None:
                    action = ("scale_in", victim)
            if action is not None:
                self._last_action_at = now
                self.history.append(
                    (round(now, 9), action[0], action[1]))
        self._m_replicas.set(len(self.router.replica_ids))
        return action

    def _scale_in_victim(self, stats) -> Optional[int]:
        """Drain-and-remove the replica whose hot prefix chains are
        cheapest to lose: primary key is the cached tokens of
        fingerprint chains held by NO other replica (migrate-based
        scale-in preserves in-flight requests but evicts the pool, so
        removing the fleet's only copy of a hot prefix re-prefills it
        from scratch for every follower), then least inflight, ties by
        highest id — retire the newest capacity first.  Fleets without
        fingerprints (prefix cache off, cold pools) score 0
        everywhere and keep the original least-loaded choice exactly.
        A drain that times out is rolled back with ``resume_replica``
        instead of failing requests."""
        holders: dict = {}
        for s in stats.values():
            for key, tokens in getattr(s, "prefix_fingerprint",
                                       {}).items():
                holders[key] = holders.get(key, 0) + 1

        def sole_hot_tokens(rid) -> int:
            fp = getattr(stats[rid], "prefix_fingerprint", {})
            return sum(tokens for key, tokens in fp.items()
                       if holders.get(key, 0) <= 1)

        victim = min(stats, key=lambda rid: (
            sole_hot_tokens(rid), stats[rid].inflight, -rid))
        ok = self.router.drain_replica(
            victim, timeout_s=self.drain_timeout_s, migrate=True)
        if not ok:
            self.router.resume_replica(victim)
            return None
        self.router.remove_replica(victim)
        self.scale_ins += 1
        self._m_in.inc()
        return victim
