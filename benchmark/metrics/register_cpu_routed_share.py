"""Share of a register pass's key-histories that the Knossos tiers sent
back to the CPU engine (the `register_cpu_routed` counter, absent when
none was), over the key-histories in the pass."""


def read(r):
    c = r["pass"].get("counters")
    if c is None:
        return None
    keys = r["runs"] * r["config"]["keys_per_run"]
    return 100.0 * c.get("register_cpu_routed", 0) / keys
