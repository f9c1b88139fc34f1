"""The PFS stand-in: one rank's link to a shared parallel file system.

Every physical read the program makes (``_read_span``, the one primitive
every storage backend implements) is charged a latency, then its bytes over
the rank's bandwidth. The latency of concurrent reads overlaps, as round
trips to a PFS do; the bandwidth is one link shared by all of the rank's
reads, so concurrent reads queue for it. The local read itself runs while
the transfer is charged, so the slower of the two sets the time.
"""
from __future__ import annotations

import threading
import time

__all__ = ["PfsLink", "open_store"]


class PfsLink:
    """Latency per read and one bandwidth shared by every reading thread."""

    def __init__(self, latency_s: float, bandwidth_bytes_per_s: float,
                 clock=time.perf_counter, sleep=time.sleep):
        if latency_s < 0 or bandwidth_bytes_per_s <= 0:
            raise ValueError((latency_s, bandwidth_bytes_per_s))
        self.latency_s = float(latency_s)
        self.bandwidth = float(bandwidth_bytes_per_s)
        self._clock = clock
        self._sleep = sleep
        self._lock = threading.Lock()
        self._link_free_at = 0.0
        #: one ``(t_issued, t_done, nbytes)`` per charged read.
        self.reads: list[tuple[float, float, int]] = []

    def read(self, nbytes: int, do_read):
        """Run ``do_read()`` as one physical read of ``nbytes`` over the link."""
        t0 = self._clock()
        self._sleep(self.latency_s)
        with self._lock:
            start = max(self._clock(), self._link_free_at)
            done = start + nbytes / self.bandwidth
            self._link_free_at = done
        out = do_read()
        wait = done - self._clock()
        if wait > 0:
            self._sleep(wait)
        t1 = self._clock()
        with self._lock:
            self.reads.append((t0, t1, int(nbytes)))
        return out


def open_store(path: str, backend: str, link: PfsLink, **options):
    """Open ``path`` through the named backend, with every read on ``link``."""
    from repro.data.backends import get_backend

    base = get_backend(backend)

    class PfsStore(base):
        def _read_span(self, start: int, stop: int):
            return link.read((stop - start) * self.sample_bytes,
                             lambda: base._read_span(self, start, stop))

    return PfsStore(path, **options)
