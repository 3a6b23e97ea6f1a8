"""Bucket dispatches per history in a sweep pass (the program's
`buckets_dispatched` counter): a count, not a time."""


def read(r):
    n = r["pass"].get("counters", {}).get("buckets_dispatched")
    return None if n is None else n / r["runs"]
