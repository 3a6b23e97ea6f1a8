"""Mean duration of the daemon's `serve_encode` spans in the window:
one request's history parsed and encoded on its connection's thread."""


def read(r):
    d = [s for n, s in r["serve_spans"] if n == "serve_encode"]
    return 1000.0 * sum(d) / len(d) if d else None
