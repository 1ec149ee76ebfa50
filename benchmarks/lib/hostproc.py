"""What ``/proc`` says of this process and its children (Linux only)."""

from __future__ import annotations

import os
from typing import Dict

_TICK = os.sysconf("SC_CLK_TCK")


def _stat_fields(pid: int | str) -> list:
    with open(f"/proc/{pid}/stat") as f:
        text = f.read()
    # The command name may hold spaces and brackets: split after it.
    return text[text.rindex(")") + 2 :].split()


def seconds_since_process_start() -> float:
    """Wall seconds since the kernel started this process: set-up time
    counts the interpreter's own start and every import."""
    start_ticks = int(_stat_fields("self")[19])
    with open("/proc/uptime") as f:
        uptime = float(f.read().split()[0])
    return uptime - start_ticks / _TICK


def tree_cpu_seconds() -> Dict[int, float]:
    """user + system CPU seconds of this process and its live direct
    children (the producers), by pid."""
    me = os.getpid()
    out: Dict[int, float] = {}
    for entry in os.listdir("/proc"):
        if not entry.isdigit():
            continue
        try:
            f = _stat_fields(entry)
        except (FileNotFoundError, ProcessLookupError, PermissionError):
            continue
        if int(entry) == me or int(f[1]) == me:
            out[int(entry)] = (int(f[11]) + int(f[12])) / _TICK
    return out
