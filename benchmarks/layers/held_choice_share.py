"""model + kernels: the share of the routers' choices that fall on the
experts this chip holds, pooled over the expert layers - the amount of
expert work the cell does (12.5 at perfect balance with 16 of 128 held;
less of it is a faster step, hence ``better: lower``).

NOT a reading of the timed window: the family's reference check counts
it in this run's set-up, at the seeded INITIAL weights, on its own seeded
rows (the mix's shapes and id range, not the producers' windows), and the
family module keeps it (same process: the runner hands a reader its
``measured`` dict alone, and the train step has no channel for a counter
of its own).  So it moves with the seed and with a change to the router
or the initialisation, never with what the timed window routes.  ``None``
for a configuration of another family, and where the program has no such
model (the family cannot be imported) or the check has not run."""


def read(m: dict):
    if (m.get("config") or {}).get("family") != "afmoe":
        return None
    try:
        from benchmarks.families import afmoe
    except ImportError:
        return None
    found = afmoe.LAST_CHECK
    return None if not found else 100.0 * found["held_choice_share"]
