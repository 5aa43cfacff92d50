"""The traced superstep's own memory peak in GB (1e9 B): ``peak_bytes`` of
the ``memory`` the program recorded from its executable's
``memory_analysis()`` (arguments, outputs and temporaries at their highest,
which ``memory_peak_bytes`` does not see). None where the backend gives no
peak or the program recorded no memory."""

from benchmark import scope_times


def read(run):
    peak = ((scope_times.program() or {}).get("memory") or {}).get("peak_bytes")
    return None if peak is None else peak / 1e9
