"""The engine thread, and the completer a solo search's fetch may move to.

The engine's state has one writer at a time: what is submitted runs one task
at a time, in order (`EngineExecutor`). A solo `_search` that has launched
its program may hand the rest of its work (the fetch, the unpacking, the
hits, the accounting, its answer) to the completer while the engine thread
plans and launches the next search (`hand_off`), so that the device and the
copy finish one search while the host works on the next. It does so only
while another search waits for the engine thread, so a lone request pays no
thread hop, and at most one search is with the completer: the engine thread
waits for it before it hands over another, and before any task that is not
a search (a refresh, a `_bulk`, a setting, an index's deletion), so that no
answer mixes two packs.

The thread that ran the search goes on as the completer, without the
engine, on the same call stack (the request's own context, so its spans,
profile events and slow log stay its own), and another thread takes the
engine and the next task.
"""

from __future__ import annotations

import contextvars
import queue
import threading
from concurrent.futures import Executor, Future
from contextlib import contextmanager, nullcontext

# the executor of the solo search that runs, and whether what runs of it
# now is followed by more of its work on the engine thread (`serial`)
_SOLO: contextvars.ContextVar["EngineExecutor | None"] = \
    contextvars.ContextVar("solo_search", default=None)
_SERIAL: contextvars.ContextVar[bool] = \
    contextvars.ContextVar("solo_serial", default=False)


class EngineExecutor(Executor):
    """One task at a time, in order, on whichever of two threads holds the
    engine. `searches_waiting()` is the number of searches submitted and not
    yet started, which the caller keeps; while it is above 0 a solo search
    hands its fetch to the completer (`hand_off`)."""

    def __init__(self, searches_waiting):
        self._searches_waiting = searches_waiting
        self._cv = threading.Condition()
        self._work_queue: queue.SimpleQueue = queue.SimpleQueue()
        self._threads: list[threading.Thread] = []
        self._engine: threading.Thread | None = None     # runs engine work
        self._completer: threading.Thread | None = None  # finishes a search
        self._parked = False  # the engine's thread waits for the completer
        self._stop = False

    # -- submission --------------------------------------------------------

    def submit(self, fn, /, *args, **kwargs) -> Future:
        """A task that is not a search: it starts once the completer is
        empty."""
        return self._put((fn, args, kwargs), False)

    def submit_search(self, fn, /, *args, **kwargs) -> Future:
        """A search: it may start while the search before it is with the
        completer."""
        return self._put((fn, args, kwargs), True)

    def _put(self, call, search: bool) -> Future:
        fut = Future()
        with self._cv:
            if self._stop:
                raise RuntimeError("cannot schedule new futures after shutdown")
            self._work_queue.put((fut, call, search))
            if not self._threads:
                self._spawn()
            self._cv.notify_all()
        return fut

    def shutdown(self, wait: bool = True, *, cancel_futures: bool = False):
        with self._cv:
            self._stop = True
            if cancel_futures:
                while not self._work_queue.empty():
                    self._work_queue.get_nowait()[0].cancel()
            self._cv.notify_all()
        if wait:
            for t in list(self._threads):
                t.join()

    # -- the threads -------------------------------------------------------

    def _spawn(self) -> None:
        t = threading.Thread(target=self._worker, daemon=True,
                             name=f"engine_{len(self._threads)}")
        self._threads.append(t)
        t.start()

    def _worker(self) -> None:
        me = threading.current_thread()
        cv = self._cv
        while True:
            with cv:
                cv.wait_for(lambda: (
                    (self._engine is None and not self._work_queue.empty())
                    or (self._stop and self._work_queue.empty())))
                if self._work_queue.empty():
                    return
                fut, (fn, args, kwargs), search = self._work_queue.get_nowait()
                self._engine = me
                if not search:
                    self._park(me)
            if fut.set_running_or_notify_cancel():
                try:
                    result = fn(*args, **kwargs)
                except BaseException as ex:  # noqa: BLE001 - to the future
                    fut.set_exception(ex)
                else:
                    fut.set_result(result)
            with cv:
                if self._engine is me:
                    self._engine = None
                else:  # the task finished on the completer
                    self._completer = None
                cv.notify_all()

    def _park(self, me) -> None:
        """The engine's thread waits until the completer is empty (the
        condition's lock held); meanwhile the completer may take the engine
        (`engine_turn`)."""
        if self._completer is None:
            return
        self._parked = True
        self._cv.notify_all()
        self._cv.wait_for(
            lambda: self._completer is None and self._engine is me)
        self._parked = False

    # -- what a running task asks ------------------------------------------

    def hand_off(self) -> bool:
        """From a search on the engine thread whose program is launched:
        wait until the completer is empty; then, if a search waits, let
        another thread take the engine and the next task, and go on as the
        completer (True). False: the caller goes on as the engine thread."""
        if self._completer is None and self._searches_waiting() <= 0:
            return False  # nothing to wait for, nobody to hand over to
        me = threading.current_thread()
        with self._cv:
            if self._engine is not me or self._completer is me:
                return False
            self._park(me)
            if self._searches_waiting() <= 0:
                return False
            self._engine, self._completer = None, me
            if len(self._threads) < 2:
                self._spawn()
            self._cv.notify_all()
        return True

    @contextmanager
    def engine_turn(self):
        """Around engine work that a search on the completer still has to do
        (a retry after the device ran out of memory): the engine is taken
        while its thread is idle or waits for the completer, and given back
        after."""
        me = threading.current_thread()
        with self._cv:
            borrow = self._completer is me and self._engine is not me
            if borrow:
                self._cv.wait_for(
                    lambda: self._engine is None or self._parked)
                owner, self._engine = self._engine, me
        try:
            yield
        finally:
            if borrow:
                with self._cv:
                    self._engine = owner
                    self._cv.notify_all()

    def drain(self) -> None:
        """Wait until no search is with the completer: before work that
        changes what such a search still reads (a refresh's new pack). Called
        on the completer itself, nothing to wait for."""
        me = threading.current_thread()
        with self._cv:
            if self._completer is None or self._completer is me:
                return
            if self._engine is me:
                self._park(me)
            else:
                self._cv.wait_for(lambda: self._completer is None)

    @contextmanager
    def solo(self):
        """Around a solo `_search` on the engine thread: once its program is
        launched, the rest of its work may move to the completer
        (`hand_off()`), and what it changes of what the completer reads
        waits for it (`drain()`)."""
        token = _SOLO.set(self)
        try:
            yield
        finally:
            _SOLO.reset(token)


def hand_off() -> bool:
    """`EngineExecutor.hand_off` of the solo search the caller runs; False
    where none may hand off."""
    ex = _SOLO.get()
    return ex is not None and not _SERIAL.get() and ex.hand_off()


@contextmanager
def serial():
    """Around work of a solo search after which more of it follows on the
    engine thread (one index of several): nothing inside hands off."""
    token = _SERIAL.set(True)
    try:
        yield
    finally:
        _SERIAL.reset(token)


def drain() -> None:
    """`EngineExecutor.drain` from the solo search the caller runs. Any
    other task of the engine thread starts with the completer empty."""
    ex = _SOLO.get()
    if ex is not None:
        ex.drain()


def engine_turn():
    """`EngineExecutor.engine_turn` of the solo search the caller runs."""
    ex = _SOLO.get()
    return nullcontext() if ex is None else ex.engine_turn()
