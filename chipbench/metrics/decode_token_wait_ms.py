"""Mean ``decode.token_wait`` span, in ms: the host blocked on the
sampled token, through its step's device work and whatever other
workers' programs ran ahead of it."""
from chipbench import spans


def read(run):
    return spans.mean_ms(run, "decode.token_wait")
