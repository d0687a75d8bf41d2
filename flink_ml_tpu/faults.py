"""Deterministic fault injection for exercising recovery paths.

Reference: the reference proves its fault-tolerance story with *injected*
failures — ``BoundedAllRoundCheckpointITCase`` wires a FailingMap that throws
after N records, restart strategies kick in, and the test asserts the job
converges to the identical result. Nothing like that is possible here unless
the failure sites are first-class: this module gives the runtime **named
fault points** at its recovery-relevant seams (epoch boundary, checkpoint
save, spill write/read, streamed window dispatch, online step) and a
deterministic way to arm them, so CI can prove the supervised execution layer
(``flink_ml_tpu/execution``) actually recovers.

Design:

- Every fault point is registered in ``FAULT_POINTS`` (name → description) and
  its seam calls ``faults.trip("<name>", **context)``. A trip on an unarmed
  point is a few dict lookups — negligible next to an epoch of training.
- Arming is programmatic (``faults.arm``) or config/env-driven
  (``FLINK_ML_TPU_FAULTS_SPEC="checkpoint.save:at=2;iteration.epoch:prob=0.05,seed=7"``)
  so a soak job can inject faults without code changes.
- Two triggers, both deterministic:
    * one-shot — fire on exactly the ``at``-th hit (1-based), then disarm;
    * seeded-probabilistic — fire per hit with probability ``prob`` from a
      ``random.Random(seed)`` stream, so a run is exactly reproducible.
- A fired point raises ``InjectedFault`` — always classified retryable by the
  supervisor's error classifier, which is what lets recovery tests drive the
  restart machinery end-to-end.

graftcheck's ``fault-points`` rule (``tools/graftcheck/rules/fault_points.py``)
asserts every registered point is exercised by at least one test, so injection
seams cannot silently rot.
"""
from __future__ import annotations

import random
import threading
from typing import Any, Dict, Optional

__all__ = [
    "FAULT_POINTS",
    "InjectedFault",
    "FaultInjector",
    "faults",
]


#: The runtime's injection seams. Adding a point here without a ``trip`` call
#: site AND a test exercising it fails graftcheck's ``fault-points`` rule.
FAULT_POINTS: Dict[str, str] = {
    "iteration.epoch": (
        "Epoch boundary of both iteration drivers (iteration/iteration.py) — "
        "the FailingMap analogue: kill training between any two epochs."
    ),
    "checkpoint.save": (
        "Entry of CheckpointManager.save (checkpoint.py) — a crash before the "
        "atomic rename leaves only a .tmp orphan, never a half snapshot."
    ),
    "datacache.spill.write": (
        "Capacity-cache chunk spill to disk (iteration/datacache.py append) — "
        "the spill-file I/O failure class."
    ),
    "datacache.spill.read": (
        "Capacity-cache spilled-chunk read-back (iteration/datacache.py) — "
        "a lost/unreadable spill file at replay time."
    ),
    "streaming.window": (
        "Streamed-training window dispatch (iteration/streaming.py "
        "run_windows) — kill a larger-than-HBM fit between micro-batch runs."
    ),
    "online.step": (
        "Online training step (models/online.py SnapshotDriver) — kill an "
        "unbounded fit after the mini-batch was pulled but before the model "
        "version commits; recovery must replay the in-flight batch."
    ),
    "serving.swap": (
        "Model-version load inside the serving hot-swap path "
        "(serving/registry.py ModelVersionPoller) — a bad published version "
        "must be skipped with a fallback to the newest older intact one, and "
        "the in-service model must keep serving untouched."
    ),
    "loop.publish": (
        "Continuous-learning publish step (loop/trainer.py) — kill the loop "
        "after a model version trained but before its servable save/rename "
        "lands; recovery must republish the lagging version without reusing "
        "or skipping a version number."
    ),
    "loop.swap": (
        "Continuous-learning swap step (loop/loop.py) — kill the loop between "
        "a publish and the warmed atomic flip; the in-service version must "
        "keep serving and the retry must complete the flip."
    ),
    "loop.rollback": (
        "Drift rollback (loop/rollback.py) — kill the loop after a regression "
        "verdict but before the revert-to-N-1 flip; the retry must finish the "
        "quarantine + rollback with zero serving errors in between."
    ),
    "serving.admit": (
        "Serving admission seam (serving/batcher.py submit) — fail a request "
        "at the queue door under live traffic; the caller sees a typed "
        "synchronous failure and the queue state stays consistent (nothing "
        "half-admitted, no deadlock)."
    ),
    "serving.dispatch": (
        "Serving batch dispatch seam (serving/batcher.py _run_batch) — kill "
        "a claimed batch after padding but before device dispatch; every "
        "claimed request must resolve exactly once with the typed fault and "
        "the next batch must serve normally."
    ),
    "loadgen.tick": (
        "Open-loop load-generator arrival tick (loadgen/generator.py) — drop "
        "an arrival mid-schedule; the harness must record the loss and keep "
        "the rest of the schedule on time (chaos-under-load runs arm this to "
        "prove the measurement rig itself survives faults)."
    ),
    "plancache.load": (
        "Plan-cache entry deserialization (servable/plancache.py "
        "PlanCache.load) — kill a warmup/rebuild mid-deserialize; the entry "
        "must be quarantined with the checkpoint-corrupt semantics and the "
        "chain must fall back to a live compile (fail-open, never wrong), "
        "with serving unaffected."
    ),
    "plancache.write": (
        "Plan-cache entry write (servable/plancache.py PlanCache.store) — "
        "kill a store mid-write, leaving a torn .tmp orphan on disk; the "
        "final entry must never become visible (tmp+rename discipline), the "
        "compiled chain keeps serving, and a later cache init sweeps the "
        "orphan."
    ),
    "fleet.dispatch": (
        "FleetRouter dispatch seam (fleet/router.py) — fail a request at the "
        "moment it is routed to a replica (primary or retry); the caller "
        "sees the typed fault, the chosen replica's in-flight accounting "
        "stays balanced, and the next dispatch routes normally."
    ),
    "fleet.respawn": (
        "ReplicaSupervisor respawn seam (fleet/supervisor.py) — fail a "
        "respawn attempt of an ejected replica; the execution.Supervisor "
        "restart strategy must retry it and the slot must re-admit only "
        "after a later attempt produces a healthy, warmed replica."
    ),
    "fleet.promote": (
        "CanaryController promotion seam (fleet/canary.py) — kill a "
        "fleet-wide rolling promotion before any replica has flipped; the "
        "canary keeps serving its bounded slice, no replica is left on a "
        "half-promoted version, and a retried promotion completes exactly "
        "once."
    ),
    "telemetry.journal": (
        "Flight-recorder journal write (telemetry/journal.py _write_record) — "
        "kill the writer thread mid-record, leaving a torn tail line on "
        "disk; the reader must tolerate it and a new incarnation must "
        "resume the sequence (no reuse) and emit a crash-resume incident "
        "bundle."
    ),
}


class InjectedFault(RuntimeError):
    """Raised when an armed fault point fires. Always retryable."""

    def __init__(self, point: str, hit: int, context: Optional[dict] = None):
        self.point = point
        self.hit = hit
        self.context = dict(context or {})
        detail = f" ({self.context})" if self.context else ""
        super().__init__(f"injected fault at {point!r} on hit {hit}{detail}")


class _Armed:
    """One armed fault point: a one-shot or seeded-probabilistic trigger."""

    def __init__(self, point: str, at: Optional[int], prob: Optional[float], seed: int):
        if (at is None) == (prob is None):
            raise ValueError(
                f"fault point {point!r}: arm with exactly one of at=<hit> "
                f"(one-shot) or prob=<p> (seeded-probabilistic)"
            )
        if at is not None and at < 1:
            raise ValueError(f"fault point {point!r}: at must be >= 1, got {at}")
        if prob is not None and not 0.0 <= prob <= 1.0:
            raise ValueError(f"fault point {point!r}: prob must be in [0, 1], got {prob}")
        self.point = point
        self.at = at
        self.prob = prob
        self.rng = random.Random(seed) if prob is not None else None
        self.hits = 0
        self.fires = 0

    def should_fire(self) -> bool:
        self.hits += 1
        if self.at is not None:
            return self.hits == self.at
        return self.rng.random() < self.prob


class FaultInjector:
    """Process-local registry of armed fault points.

    The module-level ``faults`` singleton is what the runtime seams call; tests
    arm/disarm through it and MUST ``reset()`` afterwards (the recovery tests
    wrap arming in try/finally).
    """

    def __init__(self):
        self._lock = threading.Lock()
        self._armed: Dict[str, _Armed] = {}
        self._hits: Dict[str, int] = {}
        self._fires: Dict[str, int] = {}
        self._spec_loaded = False
        #: Fired-fault observers, called OUTSIDE the trip lock with
        #: (point, hit, context) just before InjectedFault raises. This is
        #: how the L1 flight recorder (flink_ml_tpu.telemetry) journals
        #: trips without this L0 module importing upward. Appended at
        #: registration time, read-only after (iteration takes a snapshot).
        self._observers: list = []
        #: Observer callbacks that raised (counted, never propagated — a
        #: broken telemetry hook must not mask the injected fault itself).
        self.observer_errors = 0

    def add_observer(self, fn) -> "FaultInjector":
        """Register ``fn(point, hit, context)`` to run when any armed point
        fires (idempotent — re-registering the same callable is a no-op)."""
        with self._lock:
            if fn not in self._observers:
                self._observers = self._observers + [fn]
        return self

    # -- arming ---------------------------------------------------------------
    def arm(
        self,
        point: str,
        at: Optional[int] = None,
        prob: Optional[float] = None,
        seed: int = 0,
    ) -> "FaultInjector":
        """Arm ``point`` with a one-shot (``at``) or probabilistic (``prob``,
        ``seed``) trigger; re-arming replaces the previous trigger."""
        self._check_registered(point)
        with self._lock:
            self._armed[point] = _Armed(point, at, prob, seed)
        return self

    def disarm(self, point: str) -> "FaultInjector":
        with self._lock:
            self._armed.pop(point, None)
        return self

    def reset(self) -> "FaultInjector":
        """Disarm everything and zero all counters (test isolation)."""
        with self._lock:
            self._armed.clear()
            self._hits.clear()
            self._fires.clear()
            self._spec_loaded = True  # an explicit reset overrides the env spec
        return self

    def armed(self, point: str) -> bool:
        with self._lock:
            return point in self._armed

    # -- config/env spec ------------------------------------------------------
    def load_spec(self, spec: Optional[str] = None) -> "FaultInjector":
        """Arm points from a spec string: ``point[:k=v[,k=v...]]`` entries
        joined by ``;``. Keys: ``at`` (int), ``prob`` (float), ``seed`` (int);
        a bare ``point`` means ``at=1``. ``None`` reads the runtime config tier
        (``Options.FAULT_INJECTION`` / env ``FLINK_ML_TPU_FAULTS_SPEC``)."""
        if spec is None:
            from flink_ml_tpu.config import Options, config

            spec = config.get(Options.FAULT_INJECTION)
        if not spec:
            return self
        for entry in str(spec).split(";"):
            entry = entry.strip()
            if not entry:
                continue
            point, _, argstr = entry.partition(":")
            point = point.strip()
            kwargs: Dict[str, Any] = {}
            for kv in filter(None, (s.strip() for s in argstr.split(","))):
                key, _, value = kv.partition("=")
                key = key.strip()
                if key == "at":
                    kwargs["at"] = int(value)
                elif key == "prob":
                    kwargs["prob"] = float(value)
                elif key == "seed":
                    kwargs["seed"] = int(value)
                else:
                    raise ValueError(
                        f"fault spec entry {entry!r}: unknown key {key!r} "
                        "(expected at/prob/seed)"
                    )
            if "at" not in kwargs and "prob" not in kwargs:
                kwargs["at"] = 1
            self.arm(point, **kwargs)
        return self

    # -- the seam call --------------------------------------------------------
    def trip(self, point: str, **context) -> None:
        """Called by the runtime at fault point ``point``; raises
        ``InjectedFault`` when an armed trigger fires, else returns."""
        # Deferred spec load (importing the runtime never parses env specs
        # unless a fault point is actually reached). The claim-then-load is
        # two lock regions ON DIFFERENT state: the flag flips inside one
        # region, and load_spec (config/env reads — work that must not run
        # under the trip lock) runs outside it. The previous implementation
        # release()/acquire()d the held lock mid-`with`, which static
        # analysis cannot see — this shape is equivalent and analyzable.
        with self._lock:
            load_now = not self._spec_loaded
            if load_now:
                self._spec_loaded = True
        if load_now:
            self.load_spec()
        with self._lock:
            self._hits[point] = self._hits.get(point, 0) + 1
            armed = self._armed.get(point)
            if armed is None:
                if point not in FAULT_POINTS:
                    raise LookupError(
                        f"trip() on unregistered fault point {point!r}; add it "
                        "to flink_ml_tpu.faults.FAULT_POINTS"
                    )
                return
            fire = armed.should_fire()
            if not fire:
                return
            armed.fires += 1
            self._fires[point] = self._fires.get(point, 0) + 1
            hit = armed.hits
            if armed.at is not None:
                del self._armed[point]  # one-shot: disarm after firing
            observers = self._observers
        for observer in observers:
            try:
                observer(point, hit, context)
            except Exception:
                # Counted, not raised: telemetry must never mask the
                # injected fault itself.
                with self._lock:
                    self.observer_errors += 1
        raise InjectedFault(point, hit, context)

    # -- introspection --------------------------------------------------------
    def hits(self, point: str) -> int:
        with self._lock:
            return self._hits.get(point, 0)

    def fires(self, point: str) -> int:
        with self._lock:
            return self._fires.get(point, 0)

    def _check_registered(self, point: str) -> None:
        if point not in FAULT_POINTS:
            raise LookupError(
                f"unknown fault point {point!r}; registered points: "
                f"{sorted(FAULT_POINTS)}"
            )


faults = FaultInjector()
