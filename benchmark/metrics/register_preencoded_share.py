"""Share of a register pass's key-histories that came back from the
ingest workers already dense-encoded (the `register_keys_preencoded`
counter) over those and the ones that came back raw
(`register_keys_raw`); nothing from a program that counts neither."""


def read(r):
    c = r["pass"].get("counters") or {}
    pre = c.get("register_keys_preencoded")
    raw = c.get("register_keys_raw")
    if pre is None or raw is None or pre + raw == 0:
        return None
    return 100.0 * pre / (pre + raw)
