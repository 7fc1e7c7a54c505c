"""95th percentile of the gaps between consecutive tokens of every
request due in the window (``Request.t_tokens``), in ms; an unfinished
request counts as one missing gap (inf)."""
from chipbench import spans
from chipbench.metrics import common


def read(run):
    gaps = spans.inter_token_s(run)
    return common.pct_ms(gaps, 95) if gaps else None
