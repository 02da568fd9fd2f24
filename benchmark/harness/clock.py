"""Two clocks: what jax spent compiling, and the measured window.

``CompileClock`` is ``chip_smoke.py``'s, copied (PERF.md Open questions names
the original for deletion): ``jax.monitoring`` durations of trace, lowering,
backend compile and persistent-cache retrieval, and counts of backend
compiles and cache hits and misses.  ``mark()``/``since()`` give a phase's
share, so compiles inside the window can be counted.

``WindowClock`` is driven by the job itself: the trainer calls ``on_iter``
once per finished iteration (all of a chunk's calls come together, right
after that chunk's eval rows were fetched, so the chunk is complete on the
device) and ``on_dispatch`` before each chunk.  Warm-up chunks come first;
the window opens at the completion of the last of them and closes at the
first chunk completion at or after ``seconds``.  The job is then stopped at
the next dispatch, after that chunk's checkpoint is on disk.
"""

from __future__ import annotations

import time


class CompileClock:
    _DURATIONS = ("/jax/core/compile/",
                  "/jax/compilation_cache/cache_retrieval_time_sec")

    def __init__(self):
        from jax import monitoring

        self.compile_s = 0.0
        self.backend_compiles = 0
        self.cache_hits = 0
        self.cache_misses = 0
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    def _duration(self, name, secs, **kw):
        if name.startswith(self._DURATIONS):
            self.compile_s += secs
        if name.endswith("backend_compile_duration"):
            self.backend_compiles += 1

    def _event(self, name, **kw):
        if name.endswith("/cache_hits"):
            self.cache_hits += 1
        elif name.endswith("/cache_misses"):
            self.cache_misses += 1

    def mark(self) -> tuple:
        return (self.compile_s, self.backend_compiles)

    def since(self, mark: tuple) -> tuple:
        return (self.compile_s - mark[0], self.backend_compiles - mark[1])


class StopJob(Exception):
    """Raised into the trainer to end a job whose window has closed."""


class WindowClock:
    def __init__(self, seconds: float, warmup_chunks: int, min_chunks: int = 1,
                 on_open=None, on_close=None, now=time.perf_counter):
        self.seconds = float(seconds)
        self.warmup_chunks = int(warmup_chunks)
        self.min_chunks = int(min_chunks)
        self._on_open, self._on_close, self._now = on_open, on_close, now
        self.chunks: list[dict] = []       # every chunk: first, n, dispatched, done
        self._cur = None
        self.t_open = self.t_close = None
        self.evals: dict[int, float] = {}  # iteration -> reported valid metric

    # -- what the trainer calls -------------------------------------------
    def on_dispatch(self, site: str, iteration: int) -> None:
        if site != "dispatch":
            return
        if self.t_close is not None:
            raise StopJob()
        self._cur = {"first": int(iteration), "n": 0, "dispatched": self._now(),
                     "done": None}
        self.chunks.append(self._cur)

    def on_iter(self, iteration: int, info: dict) -> None:
        cur = self._cur
        if cur is None:      # per-iteration dispatch path: one chunk an iteration
            cur = self._cur = {"first": int(iteration), "n": 0,
                               "dispatched": self._now(), "done": None}
            self.chunks.append(cur)
        for key, val in info.items():
            if key.startswith("valid") and isinstance(val, float):
                self.evals[int(iteration)] = val
                break
        cur["n"] += 1
        if cur["done"] is not None:
            return
        cur["done"] = now = self._now()
        idx = len(self.chunks) - 1
        if idx == self.warmup_chunks - 1:
            self.t_open = now
            if self._on_open:
                self._on_open()
        elif (self.t_open is not None and self.t_close is None
              and now - self.t_open >= self.seconds
              and idx - self.warmup_chunks + 1 >= self.min_chunks):
            self.t_close = now
            if self._on_close:
                self._on_close()

    # -- what the window held ---------------------------------------------
    @property
    def window_chunks(self) -> list[dict]:
        if self.t_open is None:
            return []
        end = self.t_close if self.t_close is not None else float("inf")
        return [c for c in self.chunks[self.warmup_chunks:]
                if c["done"] is not None and c["done"] <= end]

    @property
    def window_iters(self) -> int:
        return sum(c["n"] for c in self.window_chunks)

    @property
    def window_s(self) -> float:
        if self.t_open is None or self.t_close is None:
            return 0.0
        return self.t_close - self.t_open

    @property
    def iters_done(self) -> int:
        return sum(c["n"] for c in self.chunks if c["done"] is not None)
