"""Peak bytes on the fullest chip at the window's close: the peak of
live arrays plus the peak reserved for programs' temporaries
(``Session.peak_bytes_now``)."""

from benchmark.harness import readers


def read(obs):
    return readers.peak_gb(obs)
