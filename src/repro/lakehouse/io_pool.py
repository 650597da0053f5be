"""Asynchronous I/O thread pool used to pipeline lake I/O with compute.

Reproduces the paper's §4.2 pipelining: "while I/O threads fetch column
chunks or persist edge lists, compute threads concurrently build the Vertex
IDM and subsequent edge lists".  The pool is a thin, instrumented wrapper
around ``concurrent.futures.ThreadPoolExecutor`` with:

- bounded in-flight depth (models the store's parallel stream budget),
- a task counter (``stats["tasks"]``),
- a ``map_pipelined`` helper that runs ``fetch`` on I/O threads and ``compute``
  on the caller thread, keeping ``depth`` fetches in flight ahead of compute —
  the exact producer/consumer structure of the startup loader.
- hedged ``fetch_with_backup``: if a fetch exceeds a deadline *or fails
  with a retryable fault*, a backup request is issued and the first
  **successful** completion wins (straggler + fault mitigation for slow
  object-store reads); the loser's exception is always consumed, never
  leaked to the pool as an unraised-future warning.
"""

from __future__ import annotations

import threading
from concurrent.futures import FIRST_COMPLETED, Future, ThreadPoolExecutor, wait
from typing import Callable, Iterable, Iterator, Sequence, TypeVar

T = TypeVar("T")
R = TypeVar("R")


class IOPool:
    def __init__(self, n_threads: int = 8, max_in_flight: int = 32):
        self.n_threads = n_threads
        self._pool = ThreadPoolExecutor(max_workers=n_threads, thread_name_prefix="io")
        self._sem = threading.Semaphore(max_in_flight)
        self._lock = threading.Lock()
        self.stats = {"tasks": 0, "backup_fetches": 0, "backup_wins": 0,
                      "hedged_errors": 0}

    def close(self) -> None:
        self._pool.shutdown(wait=True)

    def __enter__(self) -> "IOPool":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    # -- basic submission ----------------------------------------------------

    def submit(self, fn: Callable[..., R], *args, **kwargs) -> Future:
        self._sem.acquire()

        def _run():
            try:
                return fn(*args, **kwargs)
            finally:
                with self._lock:
                    self.stats["tasks"] += 1
                self._sem.release()

        try:
            return self._pool.submit(_run)
        except BaseException:
            # executor rejected the task (pool shut down mid-query): _run
            # will never run, so the in-flight slot it would have released
            # must be released here or the semaphore leaks one permit per
            # rejection until submit deadlocks
            self._sem.release()
            raise

    # -- pipelined map ---------------------------------------------------------

    def map_pipelined(
        self,
        items: Sequence[T],
        fetch: Callable[[T], R],
        compute: Callable[[T, R], object],
        depth: int = 4,
    ) -> list[object]:
        """For each item: ``compute(item, fetch(item))`` with fetches pipelined.

        ``fetch`` runs on I/O threads with ``depth`` requests in flight ahead
        of the (caller-thread) ``compute``; results are consumed in order so
        compute stays deterministic.
        """
        results: list[object] = []
        futures: list[tuple[T, Future]] = []
        it: Iterator[T] = iter(items)

        def _refill():
            while len(futures) < depth:
                try:
                    item = next(it)
                except StopIteration:
                    return
                futures.append((item, self.submit(fetch, item)))

        _refill()
        while futures:
            item, fut = futures.pop(0)
            payload = fut.result()
            _refill()  # keep the pipe full while we compute
            results.append(compute(item, payload))
        return results

    # -- hedged fetch (straggler + fault mitigation) ----------------------------

    def fetch_with_backup(
        self, fn: Callable[[], R], backup_after_s: float = 0.25
    ) -> R:
        """Run ``fn`` with a hedged backup; first *success* wins.

        The backup launches when the primary is still running at
        ``backup_after_s`` (classic straggler hedge) — or immediately when
        the primary *fails* before the deadline (error-promoted hedge: a
        failed future is never returned as the "winner" while an untried
        backup could still succeed).  Loser exceptions are consumed via a
        done-callback so they can't surface as unraised-future warnings.
        Only when both attempts fail does the primary's exception propagate.
        """
        primary = self.submit(fn)
        done, _ = wait([primary], timeout=backup_after_s, return_when=FIRST_COMPLETED)
        if done and primary.exception() is None:
            return primary.result()
        with self._lock:
            self.stats["backup_fetches"] += 1
            if done:  # primary already failed: hedge promoted by the error
                self.stats["hedged_errors"] += 1
        backup = self.submit(fn)
        futures = (primary, backup)
        pending = {f for f in futures if not f.done()}
        while True:
            for fut in futures:  # prefer primary when both landed together
                if fut.done() and fut.exception() is None:
                    if fut is backup:
                        with self._lock:
                            self.stats["backup_wins"] += 1
                    loser = backup if fut is primary else primary
                    loser.add_done_callback(lambda f: f.exception())
                    return fut.result()
            if not pending:
                break
            _, pending = wait(pending, return_when=FIRST_COMPLETED)
        # both attempts failed: surface the primary's exception (the backup's
        # is consumed above the raise so neither future leaks unraised)
        backup.exception()
        raise primary.exception()


def prefetch_iter(
    pool: IOPool, items: Iterable[T], fetch: Callable[[T], R], depth: int = 4
) -> Iterator[tuple[T, R]]:
    """Generator flavour of :meth:`IOPool.map_pipelined`."""
    futures: list[tuple[T, Future]] = []
    it = iter(items)

    def _refill():
        while len(futures) < depth:
            try:
                item = next(it)
            except StopIteration:
                return
            futures.append((item, pool.submit(fetch, item)))

    _refill()
    while futures:
        item, fut = futures.pop(0)
        value = fut.result()
        _refill()
        yield item, value
