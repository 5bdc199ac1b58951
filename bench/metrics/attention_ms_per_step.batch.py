"""Device time of the decode-chunk program's ``attention`` scope (RoPE, the
cache write, scores, softmax, values) in the traced part of the window /
the decode steps it ran."""

from bench.harness.readers import decode_scopes


def read(run):
    scopes = decode_scopes(run)
    if scopes is None or "attention" not in scopes[0]:
        return None
    seconds, steps = scopes
    return 1e3 * seconds["attention"] / steps
