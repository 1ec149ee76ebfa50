"""window rings: host time per window inside the drain-time integrity
verify (``consumer.verify``: header checks + the CRC of every payload
byte, serial or folded over spans), part of ``ring_wait_host_share``.
Host clock: what the train loop's thread spent, not what the device
lost.  ``None`` on a program without the timer."""


def read(m: dict):
    windows = m["counters"].get("consumer.windows")
    total = m["counters"].get("consumer.verify.total_s")
    if not windows or total is None:
        return None
    return 1e3 * total / windows
