"""Named locks and the runtime lock-order sanitizer.

Every lock in the tree is constructed through :func:`named_lock` /
:func:`named_rlock` / :func:`named_condition` (ddl-lint DDL024 enforces
it), which buys two things:

- **Identity.**  ``tools/ddl_verify``'s whole-program passes key the
  static lock-acquisition graph on these names, so a cross-module
  inversion (the gap DDL008/DDL006 cannot see — each looks at one
  function body) is reportable as ``"staging.pool" -> "cache.store"``
  with a call-chain witness instead of an anonymous ``<locked _thread
  .lock object>``.
- **A runtime witness.**  When a :class:`LockOrderSanitizer` is armed
  (the ``faults.py`` arming pattern), the factories return thin proxies
  that record actual per-thread acquisition stacks and flag any
  acquisition that inverts :data:`LOCK_ORDER` — the TSan-style dynamic
  half of the VP001 static pass.  Violations carry both lock names, the
  thread, and the full held-stack, and dump through the PR-15 flight
  recorder so a chaos-run inversion leaves an artifact.

Design constraints (the fault-engine contract):

- **Zero cost disarmed.**  With no sanitizer armed the factories return
  the *raw* ``threading`` primitives — not a wrapper, the actual
  ``_thread.lock``/``RLock``/``Condition`` object.  The disarmed
  "overhead" is one module-attribute read at construction time and
  nothing at all per acquire.
- **Arm before construction.**  The sanitizer observes locks
  constructed while it is armed; arming after a pipeline is built
  watches nothing (tests arm first, then build — the ``faults.armed``
  usage shape).
"""

from __future__ import annotations

import threading
from typing import Any, Dict, List, Optional, Tuple

#: The declared whole-program lock hierarchy, OUTERMOST first.  A thread
#: already holding a lock may only acquire locks that appear LATER in
#: this list (same name re-acquisition is reentrancy, allowed — named
#: re-entrant locks and sibling instances share a rank).  ``tools/
#: ddl_verify`` VP001 checks the static acquisition graph against this
#: order and that every ``named_*`` literal in the tree appears here;
#: the armed sanitizer enforces it on real executions.
LOCK_ORDER: Tuple[str, ...] = (
    # control plane (outermost: they fan out into everything below)
    "cluster.supervisor",
    "cluster.membership",
    # Fabric admission authority (serve.fabric): the apply path holds
    # the fabric lock while consulting the job registry and driving the
    # scheduler, never the reverse.
    "serve.fabric",
    "serve.fabric.jobs",
    "serve.tenancy.cond",
    "resilience.guard",
    # consumer-side orchestration
    "transport.connection",
    "resilience.ckpt.cv",
    "staging.executor.cv",
    "staging.pool",
    # data-plane rings and exchange
    "transport.shm.build",
    "transport.ring.cond",
    # Device-tier exchange board (DeviceExchangeFabric) ranks above the
    # host board: the device tier LATCHES to the host exchange, never
    # the reverse (the fallback re-run happens after the fabric lock is
    # released, but the rank still documents the one-way layering).
    "shuffle.device.cond",
    "shuffle.exchange.cond",
    "shuffle.sweep",
    # shard cache tiers
    "cache.registry",
    "cache.store",
    "cache.store.spill",
    "cache.backend",
    # leaf utilities: reachable from under ANY of the above (fault
    # points fire inside ring waits; metrics/span appends happen under
    # data-plane locks), so they must order innermost.
    "integrity.pool",
    "faults.plan",
    "obs.metrics",
    "obs.spans",
    "obs.startup",
    "obs.recorder.dump",
)

_RANK: Dict[str, int] = {name: i for i, name in enumerate(LOCK_ORDER)}


class LockOrderViolation(RuntimeError):
    """An armed sanitizer observed an acquisition inverting LOCK_ORDER."""


class LockOrderSanitizer:
    """Records per-thread lock-acquisition stacks and flags inversions.

    ``violations`` is the witness list: one ``(acquiring, holding,
    thread_name, held_stack)`` tuple per observed inversion.  ``edges``
    records every distinct ``(holding_top, acquiring)`` pair seen, so a
    test can also assert the *observed* order agrees with the static
    graph.  ``strict=True`` raises :class:`LockOrderViolation` at the
    inversion site (the deterministic-repro mode); the default records
    and dumps a flight-recorder witness but lets the run proceed (the
    chaos-leg mode — the assertion happens at the end of the test).
    """

    def __init__(
        self,
        order: Optional[Tuple[str, ...]] = None,
        strict: bool = False,
    ):
        ranks = order if order is not None else LOCK_ORDER
        self.rank: Dict[str, int] = {n: i for i, n in enumerate(ranks)}
        self.strict = strict
        self.violations: List[Tuple[str, str, str, Tuple[str, ...]]] = []
        self.edges: set = set()
        #: Approximate acquisition count (racy increment by design — it
        #: exists so a test can assert the armed run was non-vacuous,
        #: not as a metric).
        self.n_acquisitions = 0
        self._tls = threading.local()
        # Bare lock on purpose (this module IS the factory): guards the
        # shared violation/edge records, never held across user code.
        self._lock = threading.Lock()  # ddl-lint: disable=DDL024

    # -- per-thread stack bookkeeping (called from the proxies) ------------

    def _stack(self) -> List[str]:
        stack = getattr(self._tls, "stack", None)
        if stack is None:
            stack = self._tls.stack = []
        return stack

    def check(self, name: str) -> None:
        """Order check BEFORE the underlying acquire (never blocks)."""
        self.n_acquisitions += 1
        stack = self._stack()
        if not stack:
            return
        rank = self.rank.get(name)
        top = stack[-1]
        if top != name:
            with self._lock:
                self.edges.add((top, name))
        if rank is None:
            return
        for held in stack:
            held_rank = self.rank.get(held)
            if held == name or held_rank is None:
                continue  # reentrancy / unranked: no order claim
            if held_rank > rank:
                witness = (name, held, threading.current_thread().name,
                           tuple(stack))
                with self._lock:
                    self.violations.append(witness)
                self._flight_dump(name, held)
                if self.strict:
                    raise LockOrderViolation(
                        f"acquiring {name!r} while holding {held!r} "
                        f"inverts LOCK_ORDER (held stack: {stack})"
                    )

    def push(self, name: str) -> None:
        self._stack().append(name)

    def pop(self, name: str) -> None:
        stack = self._stack()
        # Release order may legitimately differ from acquire order
        # (cond.wait drops its own lock mid-stack): remove the newest
        # matching entry, not blindly the top.
        for i in range(len(stack) - 1, -1, -1):
            if stack[i] == name:
                del stack[i]
                return

    def _flight_dump(self, acquiring: str, holding: str) -> None:
        # Lazy import (the faults.py pattern): the sanitizer must not
        # pull the obs layer into processes that never arm a recorder.
        from ddl_tpu.obs import recorder as _flight

        if _flight.armed_recorder() is not None:
            _flight.flight_dump(
                f"lockorder.inversion.{holding}->{acquiring}"
            )


class _SanitizedLock:
    """Proxy over a ``threading.Lock``/``RLock`` reporting to a sanitizer."""

    __slots__ = ("name", "_inner", "_san")

    def __init__(self, name: str, inner: Any, san: LockOrderSanitizer):
        self.name = name
        self._inner = inner
        self._san = san

    def acquire(self, blocking: bool = True, timeout: float = -1) -> bool:
        self._san.check(self.name)
        got = self._inner.acquire(blocking, timeout)
        if got:
            self._san.push(self.name)
        return got

    def release(self) -> None:
        self._inner.release()
        self._san.pop(self.name)

    def locked(self) -> bool:
        return self._inner.locked()

    def __enter__(self) -> "_SanitizedLock":
        self.acquire()
        return self

    def __exit__(self, *exc: Any) -> None:
        self.release()


class _SanitizedCondition:
    """Proxy over ``threading.Condition`` reporting to a sanitizer.

    ``wait``/``wait_for`` drop the lock inside the inner primitive, so
    the held-stack entry is popped for the duration of the wait and
    re-pushed (no re-check: the thread logically still owns its slot in
    the order) when the wait returns.
    """

    __slots__ = ("name", "_inner", "_san")

    def __init__(self, name: str, inner: Any, san: LockOrderSanitizer):
        self.name = name
        self._inner = inner
        self._san = san

    def acquire(self, *args: Any, **kw: Any) -> bool:
        self._san.check(self.name)
        got = self._inner.acquire(*args, **kw)
        if got:
            self._san.push(self.name)
        return got

    def release(self) -> None:
        self._inner.release()
        self._san.pop(self.name)

    def wait(self, timeout: Optional[float] = None) -> bool:
        self._san.pop(self.name)
        try:
            return self._inner.wait(timeout)
        finally:
            self._san.push(self.name)

    def wait_for(self, predicate: Any, timeout: Optional[float] = None) -> Any:
        self._san.pop(self.name)
        try:
            return self._inner.wait_for(predicate, timeout)
        finally:
            self._san.push(self.name)

    def notify(self, n: int = 1) -> None:
        self._inner.notify(n)

    def notify_all(self) -> None:
        self._inner.notify_all()

    def __enter__(self) -> "_SanitizedCondition":
        self.acquire()
        return self

    def __exit__(self, *exc: Any) -> None:
        self.release()


#: The armed sanitizer, or None.  Read once at lock CONSTRUCTION — the
#: entire disarmed cost (no per-acquire read: disarmed factories hand
#: back raw primitives).
_ARMED: Optional[LockOrderSanitizer] = None


def named_lock(name: str) -> Any:
    """A ``threading.Lock`` with a sanitizer identity.  Disarmed: the
    raw primitive."""
    san = _ARMED
    if san is None:
        return threading.Lock()  # ddl-lint: disable=DDL024
    return _SanitizedLock(name, threading.Lock(), san)  # ddl-lint: disable=DDL024


def named_rlock(name: str) -> Any:
    """A ``threading.RLock`` with a sanitizer identity (reentrant
    re-acquisition of the same name is never an inversion)."""
    san = _ARMED
    if san is None:
        return threading.RLock()  # ddl-lint: disable=DDL024
    return _SanitizedLock(name, threading.RLock(), san)  # ddl-lint: disable=DDL024


def named_condition(name: str) -> Any:
    """A ``threading.Condition`` (own lock) with a sanitizer identity."""
    san = _ARMED
    if san is None:
        return threading.Condition()  # ddl-lint: disable=DDL024
    return _SanitizedCondition(name, threading.Condition(), san)  # ddl-lint: disable=DDL024


def arm_sanitizer(
    san: Optional[LockOrderSanitizer],
) -> Optional[LockOrderSanitizer]:
    """Arm ``san`` process-wide (``None`` disarms); returns the previous
    one.  Only locks constructed while armed are sanitized."""
    global _ARMED
    prev = _ARMED
    _ARMED = san
    return prev


def armed_sanitizer() -> Optional[LockOrderSanitizer]:
    return _ARMED


class sanitized:
    """Context manager: arm a fresh sanitizer for a scoped run.

    ::

        with concurrency.sanitized() as san:
            run_pipeline()          # locks built inside are watched
        assert not san.violations

    Restores the previously armed sanitizer on exit, even when the run
    under test raises (the ``faults.armed`` shape).
    """

    def __init__(self, order: Optional[Tuple[str, ...]] = None,
                 strict: bool = False):
        self.sanitizer = LockOrderSanitizer(order=order, strict=strict)
        self._prev: Optional[LockOrderSanitizer] = None

    def __enter__(self) -> LockOrderSanitizer:
        self._prev = arm_sanitizer(self.sanitizer)
        return self.sanitizer

    def __exit__(self, *exc: Any) -> None:
        arm_sanitizer(self._prev)
