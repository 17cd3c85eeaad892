"""Work made ahead on the host: a thread of its own fills a bounded queue,
so that a timed unit only takes what it needs and no generation falls
inside it."""
from __future__ import annotations

import queue
import threading


class Producer:
    """Calls ``make(i)`` for i = 0, 1, 2, ... on a thread of its own and
    keeps up to ``depth`` results ready, in order. ``make`` uses numpy
    only: the thread never touches JAX."""

    def __init__(self, make, depth: int, name: str):
        self._make = make
        self._queue = queue.Queue(maxsize=int(depth))
        self._stop = threading.Event()
        self.waits = 0      # takes that found nothing ready
        self._thread = threading.Thread(target=self._run, name=name,
                                        daemon=True)
        self._thread.start()

    def _run(self):
        i = 0
        while not self._stop.is_set():
            try:
                item = self._make(i)
            except Exception as e:      # raised again by the taker
                item = e
            while not self._stop.is_set():
                try:
                    self._queue.put(item, timeout=0.1)
                    break
                except queue.Full:
                    continue
            if isinstance(item, Exception):
                return
            i += 1

    def take(self):
        """The next result, in order; waits where none is ready."""
        if self._queue.empty():
            self.waits += 1
        item = self._queue.get()
        if isinstance(item, Exception):
            raise item
        return item

    def close(self) -> None:
        """Stop the thread and wait until it has ended."""
        self._stop.set()
        while True:
            try:
                self._queue.get_nowait()
            except queue.Empty:
                break
        self._thread.join(timeout=30)
        if self._thread.is_alive():
            raise RuntimeError(f"the producer {self._thread.name} did not "
                               f"stop")
