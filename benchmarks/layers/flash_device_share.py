"""model + kernels: share of the traced window the chips spent in the
flash-attention kernels (op families ``ddl_flash_fwd``,
``ddl_flash_bwd_dq``, ``ddl_flash_bwd_dkv``; own time, averaged over
the chips).  ``None`` where none of them is among the reduction's ten
largest families, or the program does not name its kernels."""


def read(m: dict):
    trace = m.get("trace")
    if not trace:
        return None
    secs = [s for name, s in trace["device_ops"] if name.startswith("ddl_flash_")]
    if not secs:
        return None
    return 100.0 * sum(secs) / trace["window_s"]
