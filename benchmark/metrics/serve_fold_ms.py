"""Mean duration of the daemon's `serve_fold` spans in the window: one
shared device dispatch of admitted histories, rendered to verdicts."""


def read(r):
    d = [s for n, s in r["serve_spans"] if n == "serve_fold"]
    return 1000.0 * sum(d) / len(d) if d else None
