"""Share of a sweep pass's runs that took the condensation route (the
program's `histories_condensed` counter) over the runs of the pass;
nothing from a program that counts no such route."""


def read(r):
    n = (r["pass"].get("counters") or {}).get("histories_condensed")
    return None if n is None else 100.0 * n / r["runs"]
