"""Mean device time of the decode program (``XLA Modules`` events of
``jit_serve_decode`` that start in the window), in ms."""
from chipbench import spans
from chipbench.metrics import common


def read(run):
    evs = spans.program_events(run, spans.DECODE_PROGRAM)
    return common.mean_ms(e.dur for e in evs or ())
