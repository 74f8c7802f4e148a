"""Small shared utilities: sleep/retry/MapDef/hex.

Mirror of the reference's `@lodestar/utils` surface the framework uses
(reference: packages/utils/src/{sleep,retry,map,bytes}.ts).
"""

from __future__ import annotations

import threading
import time
from typing import Callable, Dict, Optional, TypeVar

K = TypeVar("K")
V = TypeVar("V")
T = TypeVar("T")


class ErrorAborted(Exception):
    pass


class AbortSignal:
    """Cooperative cancellation token (the reference uses DOM
    AbortSignals; a threading.Event is the host-side equivalent)."""

    def __init__(self):
        self._event = threading.Event()

    def abort(self) -> None:
        self._event.set()

    @property
    def aborted(self) -> bool:
        return self._event.is_set()

    def sleep(self, seconds: float) -> None:
        """Sleep unless aborted; raises ErrorAborted on abort."""
        if self._event.wait(timeout=seconds):
            raise ErrorAborted()


def sleep(seconds: float, signal: Optional[AbortSignal] = None) -> None:
    if signal is None:
        time.sleep(seconds)
    else:
        signal.sleep(seconds)


class DeadlineExceeded(Exception):
    """run_with_deadline's fn did not return within its deadline."""


class DeadlineRunner:
    """`fn()` running on ONE expendable daemon thread; `wait(timeout)`
    raises DeadlineExceeded when it has not returned by then.  A stalled
    thread is abandoned — the caller moves on, and the caller's deadline
    measures ONLY its own call (no shared-worker queue wait)."""

    def __init__(self, fn: Callable[[], T], desc: str = "call"):
        self.desc = desc
        self._result: Dict[str, object] = {}
        self._done = threading.Event()
        self._t0 = time.monotonic()
        threading.Thread(
            target=self._run, args=(fn,), daemon=True, name="deadline-runner"
        ).start()

    def _run(self, fn) -> None:
        try:
            self._result["value"] = fn()
        except BaseException as e:  # noqa: BLE001 — transported to caller
            self._result["error"] = e
        finally:
            self._done.set()

    def elapsed(self) -> float:
        return time.monotonic() - self._t0

    def wait(self, timeout_s: float):
        if not self._done.wait(timeout=max(timeout_s, 0.0)):
            raise DeadlineExceeded(
                f"{self.desc} did not return within {timeout_s:g}s"
            )
        if "error" in self._result:
            raise self._result["error"]  # type: ignore[misc]
        return self._result.get("value")


def run_with_deadline(
    fn: Callable[[], T], timeout_s: float, desc: str = "call"
) -> T:
    """Run `fn()` on an expendable thread (DeadlineRunner); raise
    DeadlineExceeded when it does not return within `timeout_s`.  The
    single shared bounded-wait runner (ISSUE 14): the BLS breaker's
    watchdog and the req/resp stall timeout both wrap it with their own
    exception types."""
    return DeadlineRunner(fn, desc).wait(timeout_s)  # type: ignore[return-value]


def retry(
    fn: Callable[[], T],
    retries: int = 3,
    retry_delay: float = 0.0,
    should_retry: Optional[Callable[[Exception], bool]] = None,
    signal: Optional[AbortSignal] = None,
) -> T:
    """Call fn up to `retries` times (reference: utils/src/retry.ts)."""
    last: Optional[Exception] = None
    for attempt in range(max(retries, 1)):
        try:
            return fn()
        except Exception as e:  # noqa: BLE001 - retry boundary
            last = e
            if should_retry is not None and not should_retry(e):
                raise
            if attempt + 1 < retries and retry_delay:
                sleep(retry_delay, signal)
    assert last is not None
    raise last


class MapDef(Dict[K, V]):
    """dict with a default factory + getOrDefault (reference:
    utils/src/map.ts MapDef)."""

    def __init__(self, factory: Callable[[], V]):
        super().__init__()
        self._factory = factory

    def get_or_default(self, key: K) -> V:
        if key not in self:
            self[key] = self._factory()
        return self[key]


def to_hex(data: bytes) -> str:
    return "0x" + data.hex()


def from_hex(s: str) -> bytes:
    return bytes.fromhex(s[2:] if s.startswith("0x") else s)
