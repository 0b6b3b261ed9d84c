"""Peak bytes on the fullest chip at the window's close: the peak of
live arrays plus the peak reserved for programs' temporaries
(``Session.peak_bytes_now``). It is everything the serving process
holds, dead weight included: ``init()`` makes optimizer state that
serving never reads (PERF.md section 4 says what share is live)."""

from benchmark.harness import readers


def read(obs):
    return readers.peak_gb(obs)
