"""Mean duration of the daemon's `serve_admission_wait` spans in the
window: one request admitted (encoded and queued) until its fold
starts."""


def read(r):
    d = [s for n, s in r["serve_spans"] if n == "serve_admission_wait"]
    return 1000.0 * sum(d) / len(d) if d else None
