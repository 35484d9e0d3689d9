"""Request sessions — the client half of the streaming front end.

A ``RequestHandle`` is what ``ServingEngine.submit`` returns: a thread-safe
incremental view of one request's output tokens. It works in both engine
modes:

* **step-driven** (tests, benches): iterating ``stream()`` or calling
  ``result()`` drives ``engine.step()`` itself until tokens arrive;
* **threaded** (``engine.start()``): a driver thread steps the engine;
  consumers block on the handle's condition variable.

Cancellation is cooperative: ``cancel()`` marks the request and the engine
releases its row/blocks at the next iteration boundary (or immediately when
called between steps).

A stream ends with its last DELIVERED token, not with the scheduler's state:
the engine finishes a request (row and blocks free) when the token reaches
the host, and under its driver thread pushes that token only once the next
program is enqueued (``docs/serving.md``, threading). The handle's own
``_ended`` flag, set under its condition by the final push or by the wake of
a cancel or an expiry, is what ``done``, ``stream()`` and ``result()`` read.
"""

from __future__ import annotations

import threading
from typing import TYPE_CHECKING, Iterator, List, Optional

import numpy as np

from .scheduler import CANCELLED, DEADLINE_EXCEEDED, FINISHED, Request

if TYPE_CHECKING:  # circular at runtime: api.py imports this module
    from .api import ServingEngine

__all__ = ["RequestHandle", "RequestCancelled", "DeadlineExceeded"]


class RequestCancelled(RuntimeError):
    """Raised by ``result()`` when the request was cancelled."""


class DeadlineExceeded(RuntimeError):
    """Raised by ``result()`` when the request's deadline expired before it
    finished — its rows/blocks were reclaimed at the iteration boundary and
    the tokens streamed so far are all there will be."""


def drive_stream(cond: threading.Condition, tokens: List[int], is_done,
                 clock, threaded, step, starvation_limit, label: str,
                 stall_msg: str,
                 timeout_s: Optional[float]) -> Iterator[int]:
    """The drive-or-wait streaming loop shared by ``RequestHandle`` and the
    fleet's ``FleetHandle``: yield tokens from ``tokens`` (a live list
    guarded by ``cond``) as they appear; in step-driven mode each starved
    pass runs one ``step()``, in threaded mode block on ``cond``. Raises
    TimeoutError past ``timeout_s`` without a token and RuntimeError with
    ``stall_msg`` after ``starvation_limit()`` consecutive progress-free
    steps. ``is_done``/``threaded``/``starvation_limit`` are callables —
    all three can change while the stream is live (request finishing, a
    driver thread starting, config reload)."""
    i = 0
    deadline = clock() + timeout_s if timeout_s is not None else None
    starved = 0
    while True:
        tok = None
        with cond:
            if i < len(tokens):
                tok = tokens[i]
                i += 1
            elif is_done():
                return
            elif threaded():
                if not cond.wait(timeout=timeout_s):
                    raise TimeoutError(
                        f"{label}: no token within {timeout_s}s")
                continue
        if tok is not None:
            deadline = clock() + timeout_s if timeout_s is not None else None
            starved = 0
            yield tok
            continue
        # step-driven: advance the driver outside the condition lock
        if deadline is not None and clock() > deadline:
            raise TimeoutError(f"{label}: no token within {timeout_s}s")
        if step():
            starved = 0
        else:
            starved += 1
            if starved > starvation_limit():
                raise RuntimeError(f"{label}: {stall_msg}")


class RequestHandle:
    """Incremental, thread-safe view of one request's generated tokens."""

    def __init__(self, engine: "ServingEngine", req: Request):
        self._engine = engine
        self._req = req
        self._cond = threading.Condition()
        self._tokens: List[int] = []
        self._ended = False     # no token will follow (guarded by _cond)

    # -- engine-side (called from ServingEngine.step under its lock) -------
    def _push(self, token: int, last: bool = False) -> None:
        """``last``: the request finished with this token, so the stream
        ends behind it."""
        with self._cond:
            self._tokens.append(int(token))
            if last:
                self._ended = True
            self._cond.notify_all()

    def _wake(self) -> None:
        """Terminal-state transition with no token of its own (cancelled,
        expired, released): end the stream and wake any blocked consumers."""
        with self._cond:
            self._ended = True
            self._cond.notify_all()

    # -- client-side -------------------------------------------------------
    @property
    def request_id(self) -> int:
        return self._req.rid

    @property
    def state(self) -> str:
        return self._req.state

    @property
    def done(self) -> bool:
        """Terminal AND every token delivered (``state`` is the scheduler's
        view, which can turn an iteration earlier under the driver thread)."""
        return self._ended

    @property
    def tokens(self) -> List[int]:
        with self._cond:
            return list(self._tokens)

    @property
    def ttft_s(self) -> Optional[float]:
        return self._req.ttft_s

    @property
    def tpot_s(self) -> Optional[float]:
        return self._req.tpot_s

    @property
    def preemptions(self) -> int:
        return self._req.preemptions

    @property
    def spec_acceptance_rate(self) -> Optional[float]:
        """Accepted / proposed draft tokens for this request (None until
        speculation proposed anything)."""
        if self._req.spec_proposed == 0:
            return None
        return self._req.spec_accepted / self._req.spec_proposed

    def fork(self, n: int, seeds: Optional[List[int]] = None
             ) -> List["RequestHandle"]:
        """Branch ``n`` parallel samples off this request at its current
        position: the siblings share every block (prompt AND generated)
        through the refcounted COW tables, inherit the tokens streamed so
        far, and diverge from the next token on — sibling ``i`` samples
        with ``seeds[i]`` (default ``seed + i + 1``). The request must be
        actively decoding."""
        return self._engine.fork(self, n, seeds=seeds)

    def cancel(self) -> bool:
        """Cancel the request; returns False when it already finished."""
        return self._engine.cancel(self)

    def stream(self, timeout_s: Optional[float] = None) -> Iterator[int]:
        """Yield tokens as they are generated. In step-driven mode this
        DRIVES the engine (each starved iteration runs one engine step); in
        threaded mode it blocks on the condition variable. Ends when the
        request finishes or is cancelled; raises TimeoutError past
        ``timeout_s`` without a token (engine clock in step-driven mode),
        and RuntimeError when the engine stops making progress entirely
        (the same starvation guard as ``ServingEngine.run``)."""
        eng = self._engine
        yield from drive_stream(
            self._cond, self._tokens, lambda: self._ended, eng.clock,
            lambda: eng.threaded, eng.step,
            lambda: 2 * eng.config.max_queue + 4,
            f"request {self._req.rid}",
            "serving stalled — no request can make progress (block pool "
            "or row count too small for the workload)", timeout_s)

    def result(self, timeout_s: Optional[float] = None) -> np.ndarray:
        """Block (or drive) until the request finishes; returns the full
        generated token array. Raises ``RequestCancelled`` on cancellation
        and ``DeadlineExceeded`` when the deadline expired mid-stream."""
        for _ in self.stream(timeout_s=timeout_s):
            pass
        if self._req.state == CANCELLED:
            raise RequestCancelled(f"request {self._req.rid} was cancelled")
        if self._req.state == DEADLINE_EXCEEDED:
            raise DeadlineExceeded(
                f"request {self._req.rid} missed its deadline "
                f"({len(self.tokens)} of {self._req.max_new_tokens} tokens "
                "generated)")
        assert self._req.state == FINISHED
        return np.asarray(self.tokens, np.int32)
