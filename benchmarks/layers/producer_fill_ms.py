"""producers: median time of one window fill, from the stamps the
benchmark's producers write (monotonic clock), fills inside the steady
window only."""


def read(m: dict):
    fills = sorted(t1 - t0 for _, _, t0, t1 in m["fills"])
    if not fills:
        return None
    return 1e3 * fills[len(fills) // 2]
