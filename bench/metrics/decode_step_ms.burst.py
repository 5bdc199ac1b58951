"""Device time of the decode-chunk program (``_decode_chunk_impl``) in the
traced part of the window / the decode steps it ran."""

from bench.harness.readers import decode_step_ms


def read(run):
    return decode_step_ms(run)
