"""Guarded execution: detect, degrade, recover.

The static verifier (PR 7) proves an emitted plan is correct *before*
it runs; this module is the runtime counterpart for everything the
verifier cannot see -- transient XLA errors, NaN-producing tiles,
stragglers, preemptions.  The pieces compose bottom-up:

``classify_error``     -- transient-vs-fatal triage.  Retrying a shape
                          or compile error just re-raises it slower;
                          retrying a preempted / flaky-interconnect
                          step usually succeeds.
``Backoff``            -- deterministic jittered exponential backoff
                          (seeded, so a replayed recovery sleeps the
                          same schedule).
``GuardedCall``        -- wraps one step function (prefill / decode /
                          train step) with a per-call deadline, output
                          validation, classified retries, and an event
                          log.  Exhausted retries raise
                          :class:`GuardExhausted` carrying a
                          machine-readable :class:`FailureReport`.
``DegradationLadder``  -- an ordered list of execution configs
                          (blockspace -> xla decode, pipelined -> sync,
                          compact -> embedded); ``step_down`` records
                          each transition so the evidence trail
                          survives the incident.
``ServerState``        -- the serving state machine's states
                          (healthy -> degraded -> draining).

Nothing here imports the kernels or the model stack: the serving and
training layers wrap their own callables.
"""
from __future__ import annotations

import dataclasses
import enum
import json
import os
import tempfile
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.errors import JaxRuntimeError

from repro.runtime.trace import span


# ---------------------------------------------------------------------------
# error taxonomy
# ---------------------------------------------------------------------------

class TransientFault(RuntimeError):
    """An error known to be transient (injected faults, explicit
    retryable conditions).  Always classified ``transient``."""


class ValidationError(RuntimeError):
    """A guarded call produced output that failed validation (NaN/inf
    screen, spot-check mismatch).  Classified ``transient``: the step
    is re-executed, not the process killed."""


class DeadlineExceeded(TimeoutError):
    """A guarded call overran its per-call deadline."""


class GuardExhausted(RuntimeError):
    """Retries exhausted (or a fatal error was classified); carries the
    structured :class:`FailureReport` as ``.report``."""

    def __init__(self, message: str, report: "FailureReport"):
        super().__init__(message)
        self.report = report


#: substrings (lowercased) marking a generic RuntimeError as transient
#: -- the gRPC/XLA status families that a retry can actually fix.
TRANSIENT_MARKERS = (
    "resource_exhausted", "resource exhausted", "deadline",
    "unavailable", "preempt", "transient", "data loss", "aborted",
    "connection reset", "socket closed", "too many open files",
    "cancelled", "injected",
)

#: substrings marking an XLA runtime error as *fatal* even though the
#: type says runtime: these are trace/compile/shape problems that will
#: fail identically on every retry.
FATAL_MARKERS = (
    "invalid_argument", "invalid argument", "unimplemented",
    "failed_precondition", "shape", "mosaic", "lowering", "dtype",
)

#: substrings of a compiler's refusal to fit a program into the chip's
#: memories (HBM, VMEM, SMEM).  Their status is RESOURCE_EXHAUSTED, like
#: a run-time allocation failure, but the same program is refused again
#: on every retry: fatal, whatever the exception type.
COMPILE_REFUSAL_MARKERS = (
    "compile permanent error", "ran out of memory in memory space",
    "would exceed memory",
)


def classify_error(exc: BaseException) -> str:
    """``"transient"`` (worth retrying) or ``"fatal"`` (re-raise now).

    Explicit transient types (:class:`TransientFault`,
    :class:`ValidationError`, :class:`DeadlineExceeded`, timeouts,
    connection errors) are transient.  Python-level programming errors
    (TypeError/ValueError/KeyError/...) are fatal, and so is a
    compile-time memory refusal (:data:`COMPILE_REFUSAL_MARKERS`).  XLA
    runtime errors are transient *unless* their message carries a
    compile/shape-family marker; generic RuntimeErrors are fatal unless
    their message carries a transient-family marker.
    """
    if isinstance(exc, (TransientFault, ValidationError, DeadlineExceeded,
                        TimeoutError, ConnectionError, BrokenPipeError)):
        return "transient"
    if isinstance(exc, (TypeError, ValueError, KeyError, IndexError,
                        AttributeError, NotImplementedError,
                        ZeroDivisionError, AssertionError)):
        return "fatal"
    msg = str(exc).lower()
    if any(m in msg for m in COMPILE_REFUSAL_MARKERS):
        return "fatal"
    if isinstance(exc, JaxRuntimeError):
        if any(m in msg for m in FATAL_MARKERS):
            return "fatal"
        return "transient"
    if isinstance(exc, (OSError, RuntimeError)):
        if any(m in msg for m in TRANSIENT_MARKERS):
            return "transient"
        return "fatal"
    return "fatal"


# ---------------------------------------------------------------------------
# backoff
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Backoff:
    """Jittered exponential backoff with a deterministic schedule.

    ``delay(attempt)`` for attempt 1, 2, ... is
    ``min(base * factor**(attempt-1), max_s)`` scaled by a uniform
    jitter in ``[1 - jitter, 1 + jitter]`` drawn from a seeded
    generator -- two guards with the same seed sleep the same schedule
    (replay determinism), two with different seeds decorrelate (no
    thundering herd after a shared incident)."""

    base_s: float = 0.05
    factor: float = 2.0
    max_s: float = 5.0
    jitter: float = 0.5
    seed: int = 0

    def __post_init__(self):
        self._rng = np.random.default_rng(self.seed)

    def delay(self, attempt: int) -> float:
        raw = min(self.base_s * self.factor ** max(attempt - 1, 0),
                  self.max_s)
        if self.jitter <= 0:
            return raw
        lo, hi = 1.0 - self.jitter, 1.0 + self.jitter
        return raw * float(self._rng.uniform(lo, hi))


# ---------------------------------------------------------------------------
# validation
# ---------------------------------------------------------------------------

def _leaf_key(path) -> str:
    return "/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                    for k in path) or "<leaf>"


@jax.jit
def _finite_flags(leaves):
    """One flag per leaf: every value finite.  Runs where the leaves
    live; only the flags leave the device."""
    return jnp.stack([jnp.isfinite(x).all() for x in leaves])


@jax.jit
def _nonfinite_count(x):
    """The non-finite values of a leaf that failed the screen, counted
    in one fused program (no leaf-sized mask is kept)."""
    return jnp.sum(~jnp.isfinite(x))


def _device_verdicts(arrays: Sequence[jax.Array]) -> Tuple[List[bool], int]:
    """The screen of ``arrays`` (inexact device arrays) on their
    devices: one jitted program per set of devices the arrays live on,
    the flags copied back with one ``np.asarray`` each.  Returns the
    flags in order and the bytes copied."""
    groups: Dict[frozenset, List[int]] = {}
    for i, x in enumerate(arrays):
        groups.setdefault(frozenset(x.devices()), []).append(i)
    flags = [True] * len(arrays)
    to_host = 0
    for idx in groups.values():
        got = np.asarray(_finite_flags([arrays[i] for i in idx]))
        to_host += got.nbytes
        for i, ok in zip(idx, got):
            flags[i] = bool(ok)
    return flags, to_host


def validate_finite(out: Any, what: str = "output") -> Dict[str, int]:
    """NaN/inf screen over every floating leaf of ``out`` (every
    ``jnp.inexact`` dtype, bfloat16 included); raises
    :class:`ValidationError` naming the first offending leaf.

    Device leaves are screened where they live and only the verdict,
    one flag a leaf, is copied to the host; the count of non-finite
    values is taken only for a leaf that fails.  Host leaves (NumPy
    arrays, Python scalars) are screened on the host.

    Returns the screen's counters, which :class:`GuardedCall` puts on
    its ``guard.validate`` span: ``leaves``; ``bytes_screened``, the
    bytes of every screened leaf; ``bytes_to_host``, the bytes copied
    from a device (the verdicts); ``screened_on_device``, the leaves
    screened on the device."""
    leaves = jax.tree_util.tree_leaves_with_path(out)
    on_device = [i for i, (_, x) in enumerate(leaves)
                 if isinstance(x, jax.Array)
                 and jnp.issubdtype(x.dtype, jnp.inexact)]
    flags, to_host = _device_verdicts([leaves[i][1] for i in on_device])
    verdict = dict(zip(on_device, flags))
    screened = 0
    for i, (path, leaf) in enumerate(leaves):
        if i in verdict:
            screened += leaf.nbytes
            bad = 0 if verdict[i] else int(_nonfinite_count(leaf))
        elif isinstance(leaf, jax.Array):
            continue                       # an integer or bool device leaf
        else:
            arr = np.asarray(leaf)
            if not jnp.issubdtype(arr.dtype, jnp.inexact):
                continue
            screened += arr.nbytes
            if not np.issubdtype(arr.dtype, np.inexact):
                arr = arr.astype(np.float32)   # bfloat16, float8, ...
            bad = int(arr.size - np.isfinite(arr).sum())
        if bad:
            raise ValidationError(
                f"{what}: {bad} non-finite values in leaf "
                f"{_leaf_key(path)} (shape {np.shape(leaf)})")
    return {"leaves": len(leaves), "bytes_screened": int(screened),
            "bytes_to_host": int(to_host),
            "screened_on_device": len(on_device)}


def spot_check(reference: Any, what: str = "output",
               atol: float = 0.0) -> Callable[[Any], None]:
    """Validator factory: the guarded output must match ``reference``
    (bit-identical by default -- the repo invariant).  The serving
    layer uses this for periodic lambda-plan spot checks: recompute a
    small known-good launch and compare."""
    ref_leaves = [np.asarray(x) for x in jax.tree_util.tree_leaves(
        reference)]

    def check(out: Any) -> None:
        got = [np.asarray(x) for x in jax.tree_util.tree_leaves(out)]
        if len(got) != len(ref_leaves):
            raise ValidationError(
                f"{what}: structure mismatch vs reference "
                f"({len(got)} leaves vs {len(ref_leaves)})")
        for i, (a, b) in enumerate(zip(got, ref_leaves)):
            if a.shape != b.shape:
                raise ValidationError(
                    f"{what}: leaf {i} shape {a.shape} vs reference "
                    f"{b.shape}")
            if atol > 0:
                ok = np.allclose(a, b, atol=atol, equal_nan=False)
            else:
                ok = np.array_equal(a, b)
            if not ok:
                n_bad = int(np.sum(a != b)) if a.shape == b.shape else -1
                raise ValidationError(
                    f"{what}: leaf {i} differs from reference in "
                    f"{n_bad} elements")

    return check


# ---------------------------------------------------------------------------
# structured reporting
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class GuardEvent:
    """One observation in a guard's life: an attempt, a failure, a
    retry, a recovery, a degradation."""

    name: str                      # call-site name
    kind: str                      # ok | transient | fatal | retry |
    #                                deadline | validation | degrade
    attempt: int = 0
    error: str = ""
    elapsed_s: float = 0.0
    time: float = 0.0

    def to_json(self) -> dict:
        return dataclasses.asdict(self)


@dataclasses.dataclass
class FailureReport:
    """Machine-readable terminal failure record: what failed, how it
    was classified, what was tried, and the full event trail."""

    name: str
    error: str
    error_type: str
    classification: str
    attempts: int
    events: List[GuardEvent] = dataclasses.field(default_factory=list)
    transitions: List[dict] = dataclasses.field(default_factory=list)
    time: float = 0.0

    def to_json(self) -> dict:
        d = dataclasses.asdict(self)
        d["events"] = [e.to_json() if isinstance(e, GuardEvent) else e
                       for e in self.events]
        return d

    def write(self, path: str) -> str:
        """Atomically publish the report as JSON (tmp + rename)."""
        d = os.path.dirname(os.path.abspath(path)) or "."
        os.makedirs(d, exist_ok=True)
        fd, tmp = tempfile.mkstemp(dir=d, suffix=".report.tmp")
        try:
            with os.fdopen(fd, "w") as f:
                json.dump(self.to_json(), f, indent=1, sort_keys=True)
            os.replace(tmp, path)
        except BaseException:
            if os.path.exists(tmp):
                os.unlink(tmp)
            raise
        return path


# ---------------------------------------------------------------------------
# the guarded call
# ---------------------------------------------------------------------------

class GuardedCall:
    """Wrap a step function with deadline, validation, and classified
    jittered retries.

    >>> g = GuardedCall(decode_fn, "decode", retries=2,
    ...                 validators=[validate_finite])
    >>> logits, cache = g(params, tok, cache, pos)

    Semantics per call:

    1. run ``fn``; ``jax.block_until_ready`` the result so async
       dispatch errors surface *here*, inside the guard;
    2. if a ``deadline_s`` is set and the call overran it, record a
       ``deadline`` event (and, with ``enforce_deadline``, treat it as
       a transient failure);
    3. run every validator over the output (raising
       :class:`ValidationError` counts as a transient failure); a
       validator may return a dict of counters for the
       ``guard.validate`` span;
    4. on a transient failure: sleep the backoff, call
       ``before_retry`` (the chaos/fault-injection path uses it to
       drop poisoned executable caches), and re-execute -- up to
       ``retries`` times;
    5. on a fatal failure: raise :class:`GuardExhausted` immediately
       with the report;
    6. on exhaustion: raise :class:`GuardExhausted` with the report.

    The event log (``.events``) persists across calls; ``on_event``
    observes each event as it happens.  Each call is a ``guard.call``
    span, each attempt a ``guard.run`` span and each round of
    validators a ``guard.validate`` span (:mod:`repro.runtime.trace`).
    """

    def __init__(self, fn: Callable, name: str = "call", *,
                 retries: int = 3, backoff: Optional[Backoff] = None,
                 deadline_s: Optional[float] = None,
                 enforce_deadline: bool = False,
                 validators: Sequence[Callable[[Any], None]] = (),
                 classify: Callable[[BaseException], str] = classify_error,
                 on_event: Optional[Callable[[GuardEvent], None]] = None,
                 before_retry: Optional[Callable[[], None]] = None,
                 sleep: Callable[[float], None] = time.sleep):
        self.fn = fn
        self.name = name
        self.retries = int(retries)
        self.backoff = backoff or Backoff()
        self.deadline_s = deadline_s
        self.enforce_deadline = enforce_deadline
        self.validators = tuple(validators)
        self.classify = classify
        self.on_event = on_event
        self.before_retry = before_retry
        self.sleep = sleep
        self.events: List[GuardEvent] = []
        self.calls = 0
        self.recoveries = 0

    # -- internals ----------------------------------------------------------

    def _event(self, kind: str, attempt: int, error: str = "",
               elapsed: float = 0.0) -> GuardEvent:
        ev = GuardEvent(name=self.name, kind=kind, attempt=attempt,
                        error=error, elapsed_s=elapsed, time=time.time())
        self.events.append(ev)
        if self.on_event:
            self.on_event(ev)
        return ev

    def _report(self, exc: BaseException, classification: str,
                attempts: int) -> FailureReport:
        return FailureReport(
            name=self.name, error=str(exc),
            error_type=type(exc).__name__,
            classification=classification, attempts=attempts,
            events=list(self.events), time=time.time())

    # -- the call -----------------------------------------------------------

    def __call__(self, *args, **kwargs):
        self.calls += 1
        with span("guard.call", site=self.name):
            return self._attempts(args, kwargs)

    def _attempts(self, args, kwargs):
        attempt = 0
        while True:
            attempt += 1
            t0 = time.perf_counter()
            try:
                with span("guard.run", attempt=attempt):
                    out = self.fn(*args, **kwargs)
                    out = jax.block_until_ready(out)
                elapsed = time.perf_counter() - t0
                if self.deadline_s is not None and elapsed > self.deadline_s:
                    self._event("deadline", attempt,
                                f"{elapsed:.3f}s > {self.deadline_s:.3f}s",
                                elapsed)
                    if self.enforce_deadline:
                        raise DeadlineExceeded(
                            f"{self.name}: {elapsed:.3f}s exceeded the "
                            f"{self.deadline_s:.3f}s deadline")
                with span("guard.validate") as validating:
                    for v in self.validators:
                        counters = v(out)
                        if isinstance(counters, dict):
                            validating.set_metadata(**counters)
                self._event("ok", attempt, elapsed=elapsed)
                if attempt > 1:
                    self.recoveries += 1
                return out
            except Exception as e:  # noqa: BLE001 - triage point
                elapsed = time.perf_counter() - t0
                kind = self.classify(e)
                self._event("validation" if isinstance(e, ValidationError)
                            else kind, attempt, str(e), elapsed)
                if kind == "fatal":
                    raise GuardExhausted(
                        f"{self.name}: fatal ({type(e).__name__}): {e}",
                        self._report(e, "fatal", attempt)) from e
                if attempt > self.retries:
                    raise GuardExhausted(
                        f"{self.name}: retries exhausted after "
                        f"{attempt} attempts: {e}",
                        self._report(e, "exhausted", attempt)) from e
                delay = self.backoff.delay(attempt)
                self._event("retry", attempt, f"backoff {delay:.3f}s")
                self.sleep(delay)
                if self.before_retry is not None:
                    self.before_retry()


# ---------------------------------------------------------------------------
# degradation ladder
# ---------------------------------------------------------------------------

class DegradationLadder:
    """Ordered fallback configs, fastest/most-aggressive first.

    Each rung is an opaque dict the owner knows how to apply
    (``{"decode_kernel": "blockspace", "stages": 2}`` -> ... ->
    ``{"decode_kernel": "xla"}``).  ``step_down(reason)`` moves one
    rung and records the transition; it returns ``False`` at the
    bottom (nothing left to degrade to -- time for the failure
    report)."""

    def __init__(self, rungs: Sequence[Dict[str, Any]],
                 on_transition: Optional[Callable[[dict], None]] = None):
        if not rungs:
            raise ValueError("a ladder needs at least one rung")
        self.rungs = [dict(r) for r in rungs]
        self.level = 0
        self.transitions: List[dict] = []
        self.on_transition = on_transition

    def current(self) -> Dict[str, Any]:
        return dict(self.rungs[self.level])

    @property
    def degraded(self) -> bool:
        return self.level > 0

    def exhausted(self) -> bool:
        return self.level >= len(self.rungs) - 1

    def step_down(self, reason: str = "") -> bool:
        if self.exhausted():
            return False
        rec = {"from_level": self.level, "to_level": self.level + 1,
               "from": self.current(),
               "to": dict(self.rungs[self.level + 1]),
               "reason": reason, "time": time.time()}
        self.level += 1
        self.transitions.append(rec)
        if self.on_transition:
            self.on_transition(rec)
        return True


class ServerState(str, enum.Enum):
    """The serving state machine: HEALTHY serves at the top rung;
    DEGRADED serves on a lower rung after repeated failures; DRAINING
    stops accepting work, checkpoints decode state, and exits so a
    successor can ``elastic_restore`` and resume."""

    HEALTHY = "healthy"
    DEGRADED = "degraded"
    DRAINING = "draining"


# ---------------------------------------------------------------------------
# deterministic sampling keys
# ---------------------------------------------------------------------------

def sample_key(base_key, pos: int, batch: int):
    """Per-slot sampling keys derived from ``(seed, slot, position)``
    via ``fold_in`` -- a pure function of the coordinates, so a retried
    or replayed decode step reproduces the identical token stream
    (stateful key-splitting would advance on every retry)."""
    k = jax.random.fold_in(base_key, int(pos))
    return jax.vmap(lambda s: jax.random.fold_in(k, s))(
        jnp.arange(batch, dtype=jnp.uint32))
