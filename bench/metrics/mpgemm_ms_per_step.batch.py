"""Device time of the decode-chunk program's mpGEMM work in the traced part
of the window / the decode steps it ran: the ops the program's scopes put
under ``mpgemm``, ``mpgemm/table``, ``mpgemm/cw``, ``lm_head/mpgemm`` and
the head's ``lm_head/mpgemm/table`` and ``lm_head/mpgemm/cw``."""

from bench.harness.readers import decode_scopes, is_mpgemm


def read(run):
    scopes = decode_scopes(run)
    if scopes is None:
        return None
    seconds, steps = scopes
    return 1e3 * sum(v for k, v in seconds.items() if is_mpgemm(k)) / steps
