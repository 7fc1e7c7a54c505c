"""Mean ``decode.launch`` span, in ms: the host's work from a decode
payload's entry to its first block (token upload, the jitted step and the
sampling launches)."""
from chipbench import spans


def read(run):
    return spans.mean_ms(run, "decode.launch")
